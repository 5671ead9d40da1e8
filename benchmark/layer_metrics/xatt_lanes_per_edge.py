"""f32 lanes the graph transformer's slot passes gather an epoch per true
edge (program counter ``xatt.work``, left by the model's setup hook:
``lanes_per_epoch`` ÷ ``true_edges_per_pass``, summed over chips) — the sum
of every pass's lanes, against the least ``costmodel_xatt`` counts (2,700 in
the products-eighth cell); a max pass of its own adds K·C a layer."""

import scopered


def read(run):
    work = scopered.program_table("counters").get("xatt.work")
    if not work or not sum(work["true_edges_per_pass"]):
        return None
    return sum(work["lanes_per_epoch"]) / sum(work["true_edges_per_pass"])
