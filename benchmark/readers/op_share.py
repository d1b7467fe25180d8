"""An operation's share of the device's busy time in the traced window, in
%: the summed time of the operations whose name matches ``op`` (inside
the programs ``module`` where given) over the union of all operations on
the same chip (the first). None where no such operation ran."""


def read(state, spec):
    trace = state["trace"]
    seconds, calls = trace.op_seconds(spec["op"], spec.get("module"))
    busy = trace.busy_by_chip.get(min(trace.ops, default=0))
    if not calls or not busy:
        return None
    return 100.0 * seconds / busy
