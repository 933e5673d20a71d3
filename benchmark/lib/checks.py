"""What counts as degraded, as numbers with limits (copied from
``chip_smoke.py``'s ``check_not_degraded`` and ``_plan_routes``): a run
whose answer is right but which got there over a recovery rung, a spill or
another route than its workload names is not the system under test."""

from __future__ import annotations


def degradation() -> list:
    """No recovery event (a taken pad-ladder rung is one), no rung
    remembered, no spill, no disk page, no checkpoint."""
    from cylon_tpu.exec import checkpoint, memory, recovery
    from cylon_tpu.relational import groupby
    mem, ck = memory.stats(), checkpoint.stats()
    return [
        ("recovery_events", len(recovery.recovery_events()), 0),
        ("pad_ladder_rungs",
         sum(1 for v in groupby._PAD_CACHE.values() if v), 0),
        ("spill_events", int(mem["spill_events"]), 0),
        ("disk_events", int(mem["disk_events"]), 0),
        ("checkpoint_events", int(ck["checkpoint_events"]), 0),
    ]


def reset() -> None:
    from cylon_tpu.exec import compiler, memory, recovery
    compiler.install_listener()
    recovery.reset_events()
    memory.reset_stats()


def plan_routes(qplan) -> list:
    """``[op, route]`` of every plan node, pre-order (route None where a
    node names none)."""
    out = []

    def walk(d):
        out.append([d.get("op"), (d.get("attrs") or {}).get("route")])
        for c in d.get("children", ()):
            walk(c)
    for root in qplan.to_dict()["roots"]:
        walk(root)
    return out


def route_mismatches(seen: list, expected: list) -> int:
    """How many expected ``[op, route]`` pairs the plan does not show."""
    seen_t = [tuple(x) for x in seen]
    return sum(1 for e in expected if tuple(e) not in seen_t)
