"""JAX traces of the distributed engine's jitted stage bodies (filter,
assign, reduce, group-by) per program of the window: the ``trace`` events
that fire inside those bodies, once per trace and never on a call the
jit cache serves.  None from an engine that opens no span for its
sharded stages, and so has no such event either."""

STAGES = ("sharded_rowwise", "sharded_reduce", "sharded_groupby")


def read(run):
    if not any(s.name == "operator" and s.attrs.get("op") in STAGES
               for s in run.spans):
        return None
    return sum(s.name == "trace" for s in run.spans) / len(run.calls)
