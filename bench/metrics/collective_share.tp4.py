"""Share of the device's busy time in which a collective ran, in the
four-chip cell: per device, the union of the intervals of its collective
ops inside the window over the union of all its ops there (its busy time),
averaged over the devices.  A collective is an op whose HLO instruction
name or opcode is ``all-to-all``, ``all-gather``, ``all-reduce``,
``reduce-scatter`` or ``collective-permute``, with or without ``-start`` /
``-done`` (the async pair) and a numeric suffix; an op that only takes a
collective's result as an operand is not one.  In a v5e trace of the
48-layer model served model-parallel 4 they read ``%all_to_all.N`` (opcode
``all-to-all``), ``%psum.N`` and ``%all-reduce.N`` (``all-reduce``) and
``%all-gather.N``, none of them async: the opcode is what names them.
Nothing to read without a trace, or with no collective in the window (a
one-chip run has none).
"""
import re

from bench import trace

KINDS = r"(all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute)"
#: the instruction's own name and opcode, never its operands
COLLECTIVE = re.compile(rf"^%?{KINDS}(-start|-done)?(\.\S*)?$")


def is_collective(op_name: str) -> bool:
    m = trace.HLO.match(op_name)
    words = (m.group(1), m.group(3)) if m else (op_name.split(" ")[0],)
    return any(COLLECTIVE.match(w) for w in words)


def read(run):
    table = run.trace_table
    if table is None:
        return None
    window = trace.window_on_profile_clock(table, "bench.window")
    busy = trace.busy_by_device(table, window)
    coll = trace.busy_by_device(table, window, is_collective)
    shares = [c / b for c, b in zip(coll, busy) if b > 0]
    if not shares or not any(coll):
        return None
    return 100.0 * sum(shares) / len(shares)
