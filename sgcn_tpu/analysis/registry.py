"""The ONE registry of ``CommPlan`` consumer contract tuples.

Every module-level ``*_FIELDS*`` tuple that names ``CommPlan`` fields for
shipping/slicing must be registered here — the plan-contract lint
(``tests/test_plan_contract.py``) validates each entry against the
dataclass and the shard proxy, and the AST hygiene pass
(``ast_rules.rule_consumer_registered``) fails the commit that introduces
a new ``*_FIELDS*`` tuple anywhere in the package without registering it.
Moved here from the test module so the test, the AST rule and any future
consumer read one registry (PR-9 consolidation; the entries themselves
are unchanged since their introducing PRs).

The registry proper is PURE DATA (name → defining module attribute) so
the AST pass never imports the SCANNED modules: resolving the tuple
VALUES imports the consumers (models/ops/serve — heavy, side-effectful),
and the AST rules must never be defeated by a scanned module's
import-time behavior.
``resolve_consumer_tuples()`` does the imports for the consumers that
need values (the plan-contract lint).
"""

from __future__ import annotations

import importlib

# every tuple that names CommPlan fields for shipping/slicing, in one
# place: registered name → "defining.module:attribute" (pure strings — no
# imports at module load)
CONSUMER_TUPLE_SOURCES = {
    "PALLAS_PLAN_FIELDS": "sgcn_tpu.ops.pallas_spmm:PALLAS_PLAN_FIELDS",
    "PALLAS_PLAN_FIELDS_RAGGED":
        "sgcn_tpu.ops.pallas_spmm:PALLAS_PLAN_FIELDS_RAGGED",
    "GAT_PLAN_FIELDS_PALLAS":
        "sgcn_tpu.models.gat:GAT_PLAN_FIELDS_PALLAS",
    "GAT_PLAN_FIELDS_PALLAS_RAGGED":
        "sgcn_tpu.models.gat:GAT_PLAN_FIELDS_PALLAS_RAGGED",
    "GAT_PLAN_FIELDS": "sgcn_tpu.models.gat:GAT_PLAN_FIELDS",
    "GAT_PLAN_FIELDS_RAGGED":
        "sgcn_tpu.models.gat:GAT_PLAN_FIELDS_RAGGED",
    "MHGAT_PLAN_FIELDS": "sgcn_tpu.models.mhgat:MHGAT_PLAN_FIELDS",
    "DEEPERGCN_PLAN_FIELDS":
        "sgcn_tpu.models.deepergcn:DEEPERGCN_PLAN_FIELDS",
    "RGCN_PLAN_FIELDS": "sgcn_tpu.models.rgcn:RGCN_PLAN_FIELDS",
    "GCN_PLAN_FIELDS_SYM": "sgcn_tpu.models.gcn:GCN_PLAN_FIELDS_SYM",
    "GCN_PLAN_FIELDS_SLOTS": "sgcn_tpu.models.gcn:GCN_PLAN_FIELDS_SLOTS",
    "GCN_PLAN_FIELDS_GEN": "sgcn_tpu.models.gcn:GCN_PLAN_FIELDS_GEN",
    "GCN_PLAN_FIELDS_RAGGED":
        "sgcn_tpu.models.gcn:GCN_PLAN_FIELDS_RAGGED",
    "STALE_PLAN_FIELDS_RAGGED":
        "sgcn_tpu.parallel.plan:STALE_PLAN_FIELDS_RAGGED",
    "REPLICA_PLAN_FIELDS": "sgcn_tpu.parallel.plan:REPLICA_PLAN_FIELDS",
    "REPLICA_PLAN_FIELDS_RAGGED":
        "sgcn_tpu.parallel.plan:REPLICA_PLAN_FIELDS_RAGGED",
    "REPLICA_STALE_PLAN_FIELDS":
        "sgcn_tpu.parallel.plan:REPLICA_STALE_PLAN_FIELDS",
    "REPLICA_STALE_PLAN_FIELDS_RAGGED":
        "sgcn_tpu.parallel.plan:REPLICA_STALE_PLAN_FIELDS_RAGGED",
    "REPLICA_PARTIAL_PLAN_FIELDS":
        "sgcn_tpu.parallel.plan:REPLICA_PARTIAL_PLAN_FIELDS",
    "SERVE_ROUTER_FIELDS": "sgcn_tpu.serve.router:SERVE_ROUTER_FIELDS",
    "SERVE_SUBGRAPH_FIELDS":
        "sgcn_tpu.serve.subgraph:SERVE_SUBGRAPH_FIELDS",
}

# the two CLASSIFICATION tuples (parallel/plan.py) — not consumer tuples
# (they classify rather than ship), but legitimate *_FIELDS* names the AST
# rule must accept
CLASSIFICATION_TUPLES = ("PER_CHIP_ARRAY_FIELDS", "_GLOBAL_ARRAY_FIELDS")


def known_fields_names() -> frozenset:
    """Every ``*_FIELDS*`` name the AST rule accepts — names only, no
    consumer imports."""
    return (frozenset(CONSUMER_TUPLE_SOURCES)
            | frozenset(CLASSIFICATION_TUPLES))


def resolve_consumer_tuples() -> dict:
    """name → the live tuple, imported from its defining module — for
    consumers that validate VALUES (``tests/test_plan_contract.py``).
    Raises loudly if a registered name no longer exists (a stale registry
    entry is its own lint failure)."""
    out = {}
    for name, src in CONSUMER_TUPLE_SOURCES.items():
        mod, _, attr = src.partition(":")
        out[name] = getattr(importlib.import_module(mod), attr)
    return out
