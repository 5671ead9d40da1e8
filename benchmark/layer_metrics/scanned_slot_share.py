"""Share of the executed slots an epoch that run as a ``lax.scan`` (program
counter ``slots.work``: ``per_epoch.scanned_slots`` ÷ ``ell_slots`` +
``fold_slots``) — a count, read beside the traced prices it explains."""

import scopered_slots


def read(run):
    work = scopered_slots.counter() if run.get("trace") else None
    if not work:
        return None
    per = work["per_epoch"]
    slots = per["ell_slots"] + per["fold_slots"]
    return 100.0 * per["scanned_slots"] / slots if slots else None
