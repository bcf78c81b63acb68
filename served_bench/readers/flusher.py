"""What the sidecar's flusher thread did inside the window: differences of
its phase ledger (``health["flusher"]``, consensus_tpu/obs/kernels.py) between
the window's last and first instant.  Not a reader itself: the five readers
of the ledger share it."""


def delta(ctx: dict, *keys):
    """The sum over ``keys`` of last - first, or ``None`` where the sidecar's
    ``health`` has no ledger or lacks a key (a program from before it)."""
    first, last = ctx["first"].get("flusher"), ctx["last"].get("flusher")
    if not first or not last:
        return None
    if any(key not in first or key not in last for key in keys):
        return None
    return sum(last[key] - first[key] for key in keys)
