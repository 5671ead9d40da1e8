"""True edges ÷ executed slots of one aggregation pass, mean over chips, from
``CommPlan.work_counts()`` (left in ``sgcn_tpu.obs.tracing.counters()`` by
``build_comm_plan``): every chip executes the padded shapes (ELL buckets,
tail, halo-edge list), and only the true edges are useful outcomes."""

import scopered

EDGES = ("slot_edges", "tail_edges", "halo_edges")


def read(run):
    work = scopered.program_table("counters").get("plan.work_counts")
    if not work:
        return None
    executed = sum(work["executed"][k] for k in EDGES)
    true = [sum(chip) for chip in zip(*(work["true"][k] for k in EDGES))]
    return 100.0 * sum(t / executed for t in true) / len(true)
