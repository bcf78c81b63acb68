"""The comparison that decides ``correct``: sound readings pass, and each
fault the cell can have, planted where the answer is produced, fails it."""

import copy

import pytest

from sb_limits import limit
from served_bench import judge, reference, traffic


def _sound(n=4, f=1, requests=200, lanes=64, seed=2_147_483_700):
    factory = traffic.RequestFactory(seed, clients=8, body_bytes=64)
    sent = factory.make_many(requests)
    audit = {"decisions": 4, "requests": requests, "distinct": requests,
             "ids_digest": reference.ids_digest(sent),
             "digest": reference.ordered_digest(sent)}
    wave, planted = reference.verdict_wave(lanes, seed)
    per_client = requests * 3 // n
    in_window = []
    for k in range(3):
        w, p = reference.verdict_wave(16, seed, k + 1)
        in_window.append({"wave": w, "planted": p, "took_s": 0.1,
                          "got": reference.wave_verdicts(w)})
    return {
        "size": {"n": n, "f": f, "batch": 50, "clients": 8},
        "sent_requests": sent,
        "audits": {i + 1: dict(audit) for i in range(n)},
        "invariants_clean": True,
        "window_waves": in_window,
        "sidecar": {"device_signatures": per_client * n + 48, "host_signatures": 0,
                    "compiles_after_ready": 0, "device_suspect": False,
                    "degrade_count": 0},
        "clients": {f"replica-{i + 1}": {"sent": per_client, "served": per_client,
                                         "fallen_back": 0, "suspect": False}
                    for i in range(n)},
        "sidecar_restarts": 0,
        "teardown": {"ok": True},
        "wave": {"lanes": lanes, "planted": planted, "wave": wave,
                 "got": reference.wave_verdicts(wave),
                 "device_signatures_after": per_client * n + 48 + lanes,
                 "compiles_after_ready": 0},
    }


def _failing(readings) -> list:
    return [k for k, c in judge.compare(readings).items() if c["value"] > c["limit"]]


@limit(60)
def test_sound_readings_are_correct_and_every_limit_is_exact():
    compared = judge.compare(_sound())
    assert judge.correct(compared)
    assert set(compared) == set(judge.LIMITS)
    assert all(c["limit"] == 0 for c in compared.values())
    assert judge.order_exact(_sound())


def _half_of_the_batch_left_out(r):
    a = r["audits"][2]
    a["requests"] = a["distinct"] = a["requests"] // 2
    a["ids_digest"] = reference.ids_digest(r["sent_requests"][: a["requests"]])


def _a_request_delivered_twice(r):
    r["audits"][3]["requests"] += 1


def _an_answer_altered_on_one_replica(r):
    r["audits"][4]["digest"] = reference.ordered_digest(
        r["sent_requests"][:-1] + [r["sent_requests"][-1][:-1] + b"\x00"])


def _a_verdict_altered_where_it_is_produced(r):
    bad = next(iter(r["wave"]["planted"]))
    r["wave"]["got"][bad] = True  # a forgery waved through


def _a_verifier_that_accepts_everything(r):
    r["wave"]["got"] = [True] * r["wave"]["lanes"]


def _a_verdict_altered_inside_the_window(r):
    w = r["window_waves"][1]
    w["got"][next(iter(w["planted"]))] = True


def _everything_accepted_inside_the_window(r):
    for w in r["window_waves"]:
        w["got"] = [True] * len(w["got"])


def _a_wave_of_the_window_never_answered(r):
    w = r["window_waves"][2]
    w["got"] = None
    w["error"] = "TimeoutError()"


def _a_wave_of_the_window_answered_off_the_device(r):
    r["sidecar"]["device_signatures"] -= 16
    r["wave"]["device_signatures_after"] -= 16


def _served_from_the_host(r):
    r["clients"]["replica-1"]["fallen_back"] = 50
    r["clients"]["replica-1"]["served"] -= 50
    r["sidecar"]["device_signatures"] -= 50
    r["wave"]["device_signatures_after"] -= 50


def _compiled_inside_the_window(r):
    r["sidecar"]["compiles_after_ready"] = 1


def _the_sidecar_restarted(r):
    r["sidecar_restarts"] = 1


FAULTS = {
    _half_of_the_batch_left_out: "missing",
    _a_request_delivered_twice: "duplicated",
    _an_answer_altered_on_one_replica: "ledgers_differ",
    _a_verdict_altered_where_it_is_produced: "verdict_mismatches",
    _a_verifier_that_accepts_everything: "verdict_mismatches",
    _a_verdict_altered_inside_the_window: "verdict_mismatches",
    _everything_accepted_inside_the_window: "verdict_mismatches",
    _a_wave_of_the_window_never_answered: "verdict_mismatches",
    _a_wave_of_the_window_answered_off_the_device: "host_served",
    _served_from_the_host: "host_served",
    _compiled_inside_the_window: "compiled_after_ready",
    _the_sidecar_restarted: "unhealthy",
}


@pytest.mark.parametrize("fault", list(FAULTS), ids=lambda f: f.__name__.strip("_"))
@limit(60)
def test_a_planted_fault_comes_out_not_correct(fault):
    readings = copy.deepcopy(_sound())
    fault(readings)
    failing = _failing(readings)
    assert FAULTS[fault] in failing
    assert not judge.correct(judge.compare(readings))


@limit(60)
def test_the_reference_rejects_exactly_the_planted_lanes_on_large_seeds():
    for seed in (0, 7, 2**31 + 12345, 4_000_000_123):
        for index in (0, 1, 30):
            wave, planted = reference.verdict_wave(64, seed, index)
            verdicts = reference.wave_verdicts(wave)
            assert sorted(planted) == [i for i, ok in enumerate(verdicts) if not ok]
            assert len(set(planted.values())) == 8
        assert reference.verdict_wave(64, seed, 1) != reference.verdict_wave(64, seed, 2)


@limit(60)
def test_requests_follow_from_the_seed_alone():
    a = traffic.RequestFactory(2**31 + 9, clients=4, body_bytes=64).make_many(12)
    b = traffic.RequestFactory(2**31 + 9, clients=4, body_bytes=64).make_many(12)
    c = traffic.RequestFactory(2**31 + 10, clients=4, body_bytes=64).make_many(12)
    assert a == b and a != c
    assert all(len(raw) == 12 + 64 + 64 for raw in a)
    key = traffic.Ed25519PrivateKey.from_private_bytes(
        traffic.client_seed32(traffic.seeded_namespace(2**31 + 9), 1))
    assert reference.verify_one(traffic.REQUEST_TAG + a[1][:-64], a[1][-64:],
                                key.public_key().public_bytes_raw())
