"""Device ns per executed ELL slot: seconds an epoch under the bucket tokens
(``sgcn.bkt_*``) inside ``sgcn.agg_slots``, mean over chips, ÷ the ELL slots
the step's passes execute an epoch (program counter ``slots.work``,
``per_epoch.ell_slots``).  The ``slot_prices`` line has it by bucket."""

import scopered_slots


def read(run):
    return scopered_slots.price_ns(run, "ell_s", "ell_slots")
