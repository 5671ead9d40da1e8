"""Share of the device seconds under the three aggregation leaf scopes
(``sgcn.agg_slots``, ``agg_tail``, ``agg_halo_fold``) in ops that carry
neither a bucket token nor ``sgcn.fold_rows``: concatenations, ``out + ell``,
copies — what the slot prices cannot see, as ``unscoped_share`` is for the
scopes."""

import scopered_slots


def read(run):
    tab = scopered_slots.table(run)
    if tab is None or not tab["sums"]["agg_s"]:
        return None
    return 100.0 * tab["sums"]["unbucketed_s"] / tab["sums"]["agg_s"]
