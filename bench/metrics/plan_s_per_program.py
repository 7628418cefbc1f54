"""Self time of each ``execute`` span (its duration less its ``segment``
children: planning, plan-cache lookup, persist and sink bookkeeping) per
program of the window."""


def read(run):
    execute = [s for s in run.spans if s.name == "execute"]
    if not execute:
        return None
    child = {}
    for s in run.spans:
        if s.name == "segment":
            child[s.parent_id] = child.get(s.parent_id, 0.0) + s.duration
    return sum(s.duration - child.get(s.id, 0.0)
               for s in execute) / len(run.calls)
