"""Device ns per virtual row folded: seconds an epoch under
``sgcn.fold_rows`` (the sorted row scatter of a width class and, in the
attention layer, the gather of its destination-side rows), mean over chips,
÷ the virtual rows an epoch (program counter ``slots.work``,
``per_epoch.virtual_rows``)."""

import scopered_slots


def read(run):
    return scopered_slots.price_ns(run, "rows_s", "virtual_rows")
