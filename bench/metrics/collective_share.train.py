"""Share of the traced training window that the chips spend in collective
operations (``all-reduce``, ``reduce-scatter``, ``all-gather``,
``collective-permute``, with their async ``-start``/``-done`` halves):
their device time summed over the chips, over the window's length times
the chips used.  An op is known by its HLO opcode, the word before the
operands' parenthesis in the trace's op name: the instruction's own name
may be anything (``%psum.21 = f32[...] all-reduce(...)``), and a
collective's name among another op's operands does not count."""

import re

COLLECTIVE = re.compile(
    r"^\S+ = .*?\b(all-reduce|reduce-scatter|all-gather|collective-permute)"
    r"(-start|-done)?\(")


def read(run):
    t = run.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    busy = sum(ns for dev in t["devices"]
               for name, ns in dev["by_name"].items()
               if COLLECTIVE.match(name)) * 1e-9
    return 100.0 * busy / (t["window_s"] * run["n_devices"])
