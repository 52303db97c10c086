"""Mean device time of the jitted prefill step (prefill and mixed plans)
in the traced window."""

from chipbench import trace


def read(ctx):
    d = [e.dur_ns for e in ctx["events"] if trace.is_device(e)
         and e.line == trace.MODULE_LINE
         and trace.module_kind(e.name) == "prefill"
         and ctx["lo"] <= e.start_ns and e.end_ns <= ctx["hi"]]
    return sum(d) / len(d) / 1e6 if d else None
