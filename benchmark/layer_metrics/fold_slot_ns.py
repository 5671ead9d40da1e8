"""Device ns per executed slot of a virtual row: seconds an epoch under the
bucket tokens (``sgcn.bkt_*``) inside ``sgcn.agg_tail`` and
``sgcn.agg_halo_fold``, mean over chips, ÷ the slots of the tail's and the
halo store's width classes an epoch (program counter ``slots.work``,
``per_epoch.fold_slots``); the row scatters are ``fold_row_ns``."""

import scopered_slots


def read(run):
    return scopered_slots.price_ns(run, "fold_s", "fold_slots")
