"""Every CPU-fallback island or CPU expression bridge in a physical plan.
Copied from ``chip_smoke.fallback_nodes``: a plan that holds one does not
run the query on the device, whatever its rows say."""


def fallback_nodes(exec_plan) -> list:
    from spark_rapids_tpu.expressions.bridge import CpuBridgeExpression
    from spark_rapids_tpu.expressions.core import Expression
    from spark_rapids_tpu.expressions.parity import _BridgeExpr
    from spark_rapids_tpu.plan.execs.base import TpuExec
    from spark_rapids_tpu.plan.execs.fallback import TpuCpuFallbackExec

    found, seen = [], set()

    def walk(x):
        if id(x) in seen:
            return
        if isinstance(x, TpuExec):
            seen.add(id(x))
            if isinstance(x, TpuCpuFallbackExec):
                found.append(x.node_name())
            for v in vars(x).values():
                walk(v)
        elif isinstance(x, Expression):
            seen.add(id(x))
            if isinstance(x, (CpuBridgeExpression, _BridgeExpr)):
                found.append(type(x).__name__)
            for c in x.children:
                walk(c)
            for v in vars(x).values():
                walk(v)
        elif isinstance(x, (list, tuple)):
            for v in x:
                walk(v)
        elif isinstance(x, dict):
            for v in x.values():
                walk(v)

    walk(exec_plan)
    return found


def plan_nodes(exec_plan) -> list:
    return [ln.strip().split("[")[0]
            for ln in exec_plan.tree_string().splitlines()]
