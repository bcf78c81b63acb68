"""The comparison that decides ``correct``.

Every number compared is a count of departures from what the plain reference
(served_bench/reference.py) and the configuration's guarantees say a run must
show, so every limit is 0: an exact comparison.  The numbers:

* ``missing`` — (replica, request) pairs sent and not delivered.  Guarantee:
  every replica delivers every committed request; the run waits for each,
  up to the drain's time-out.
* ``duplicated`` — deliveries beyond the first.  Guarantee: exactly once.
* ``wrong_set`` — replicas whose order-free digest of delivered ``(client,
  seq)`` identities differs from the reference's digest of what was sent.
* ``ledgers_differ`` — replicas whose ordered digest of delivered raw
  requests differs from the first replica's.  Guarantee: one total order.
* ``invariant_breaks`` — the launcher's own ledger-prefix monitor.
* ``host_served`` — signatures the replicas sent to the sidecar that were
  answered from a host path (client fallbacks + sidecar host signatures),
  plus any the device launched short of what the replicas and the window's
  verdict waves sent.  Guarantee: every quorum signature verified on the
  device path.
* ``device_floor_short`` — signatures the device should at least have
  verified (committed requests x 2f) and did not.
* ``compiled_after_ready`` — programs compiled after the sidecar said ready.
* ``unhealthy`` — suspect clients, a suspect or degraded device, restarts,
  an unclean teardown.
* ``verdict_mismatches`` — lanes whose verdict differs from the reference's,
  in the verdict waves sent INSIDE the timed window (the harness is one more
  tenant of the sidecar, so its lanes ride launches together with the
  replicas' own; a wave not answered counts with all its lanes) and in the
  full-width wave sent after the drain (every lane position of the launch
  shape); each carries one lane of each rejection class among honest ones,
  through the same sidecar socket and the same compiled program.  Plus the
  lanes by which the full-width wave missed the device.
* ``reference_blind`` — planted lanes the reference itself accepts (it must
  reject exactly the planted ones, or the wave proves nothing).

``order_exact`` is printed beside them and is not compared: requests commit in
the order sent as long as nothing is forwarded or re-proposed, which is what
lets the k-th commit be read as the k-th request, but it is no guarantee of
the protocol.
"""

from __future__ import annotations

from served_bench import reference

LIMITS = {name: 0 for name in (
    "missing", "duplicated", "wrong_set", "ledgers_differ", "invariant_breaks",
    "host_served", "device_floor_short", "compiled_after_ready", "unhealthy",
    "verdict_mismatches", "reference_blind")}


def compare(readings: dict) -> dict:
    """``{name: {"value": v, "limit": l}}`` for every number compared."""
    sent = readings["sent_requests"]
    n_req = len(sent)
    f = readings["size"]["f"]
    audits = list(readings["audits"].values())
    want_ids = reference.ids_digest(sent)
    first_digest = audits[0].get("digest") if audits else None
    v = dict.fromkeys(LIMITS, 0)
    for a in audits:
        delivered, distinct = int(a.get("requests", 0)), int(a.get("distinct", 0))
        v["missing"] += max(0, n_req - distinct)
        v["duplicated"] += max(0, delivered - distinct)
        v["wrong_set"] += int(a.get("ids_digest") != want_ids)
        v["ledgers_differ"] += int(a.get("digest") != first_digest)
    v["invariant_breaks"] = int(not readings.get("invariants_clean", False))

    sc = readings["sidecar"]
    clients = readings["clients"].values()
    sent_sigs = sum(c.get("sent", 0) for c in clients)
    served_sigs = sum(c.get("served", 0) for c in clients)
    dev_sigs = int(sc.get("device_signatures", -1))
    window_waves = readings["window_waves"]
    tenant_sigs = sum(len(w["wave"][0]) for w in window_waves if "took_s" in w)
    v["host_served"] = (
        sum(c.get("fallen_back", 1) for c in clients)
        + int(sc.get("host_signatures", 1))
        + abs(sent_sigs - served_sigs)
        + abs(sent_sigs + tenant_sigs - dev_sigs))
    v["device_floor_short"] = max(0, n_req * 2 * f - dev_sigs)
    v["compiled_after_ready"] = int(sc.get("compiles_after_ready", 1))
    v["unhealthy"] = (
        sum(1 for c in clients if c.get("suspect", True))
        + int(sc.get("device_suspect") is not False)
        + int(sc.get("degrade_count", 1))
        + int(readings.get("sidecar_restarts", 1))
        + int(not readings.get("teardown", {}).get("ok", False))
        + abs(len(readings["clients"]) - readings["size"]["n"]))

    wave = readings["wave"]
    for w in window_waves + [wave]:
        want = reference.wave_verdicts(w["wave"])
        got = w["got"] if w["got"] is not None else []
        rejected = {i for i, ok in enumerate(want) if not ok}
        v["reference_blind"] += len(set(w["planted"]) ^ rejected)
        v["verdict_mismatches"] += (
            sum(1 for g, r in zip(got, want) if g != r)
            + abs(len(got) - len(want)))
    v["verdict_mismatches"] += (
        abs(int(wave.get("device_signatures_after") or 0) - dev_sigs
            - wave["lanes"])
        + int(wave.get("compiles_after_ready") or 0))
    return {name: {"value": int(v[name]), "limit": LIMITS[name]} for name in LIMITS}


def order_exact(readings: dict) -> bool:
    audits = list(readings["audits"].values())
    return bool(audits) and audits[0].get("digest") == reference.ordered_digest(
        readings["sent_requests"])


def correct(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values())
