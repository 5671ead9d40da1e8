"""Host seconds in ``build_comm_plan``."""


def read(run):
    spans = run["spans"].get("plan")
    return spans[0] if spans else None
