"""The decode-attention kernel's share of its roofline. It is bound by
memory: the bytes ``counts.py`` says the traced steps' contexts need
(int8 keys and values and their scales, every layer), over the HBM
peak, over the kernel's device time in the trace.

Only step executions that lie wholly in the traced window count, on
both sides of the ratio.

params: ``kernel`` (regular expression on the operation's name),
``program`` (regular expression on the step program's name).
"""

import re


def read(readings, params):
    from benchmark import counts, harness

    steps = readings.client.get("traced_step_contexts") or []
    if readings.trace is None or not steps:
        return None
    rx, prog = re.compile(params["kernel"]), re.compile(params["program"])
    seconds, executions = 0.0, 0
    for name, ops in readings.trace["op_seconds_in"].items():
        if prog.search(name):
            executions += len(readings.trace["programs"][name])
            seconds += sum(s for op, s in ops.items() if rx.search(op))
    if seconds <= 0 or not executions:
        return None  # the kernel is not on the path: nothing to read
    need = sum(counts.gpt2_decode_attn_bytes(readings.cell.config, ctx)
               for ctx in steps) / len(steps)
    peak = harness.peaks(readings.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / peak / (seconds / executions)
