"""A share of the chip's roofline for the sparse-expert decoder's decode
step, or for some of its operations: the least time the chip could take
for the work ``counts_decoder.py`` gives at the traced steps' slots and
contexts, over the device time the trace shows for it.

Only step executions that lie wholly in the traced window count, on
both sides of the ratio. Nothing to read (None) where the trace has no
such program or operation, as on a program that lacks them.

params: ``program`` (regular expression on the step program's name);
``ops`` (regular expression on the operations' names; absent: the whole
program's device time); ``work`` (``step``: the whole step's operations;
``experts``: the expert matmuls' operations and weight bytes;
``attention``: the attended keys' and values' bytes); ``bound``
(``flops``, ``bytes``, or ``max`` of the two).
"""

import re


def read(readings, params):
    from benchmark import counts_decoder as counts
    from benchmark import harness

    steps = readings.client.get("traced_step_contexts") or []
    if readings.trace is None or not steps:
        return None
    prog = re.compile(params["program"])
    runs = {name: ds for name, ds in readings.trace["programs"].items()
            if prog.search(name)}
    executions = sum(len(ds) for ds in runs.values())
    if not executions:
        return None
    if params.get("ops"):
        rx = re.compile(params["ops"])
        seconds = sum(
            s for name in runs
            for op, s in readings.trace["op_seconds_in"].get(name, {}).items()
            if rx.search(op))
    else:
        seconds = sum(sum(ds) for ds in runs.values())
    if seconds <= 0:
        return None  # the operation is not on the path: nothing to read
    cfg = readings.cell.config
    work = {
        "step": lambda c: (counts.decode_step_flops(cfg, c), 0.0),
        "experts": lambda c: (counts.expert_flops(cfg, len(c)),
                              counts.expert_bytes(cfg, len(c))),
        "attention": lambda c: (0.0, counts.decode_attn_bytes(cfg, c)),
    }[params["work"]]
    flops = sum(work(c)[0] for c in steps) / len(steps)
    nbytes = sum(work(c)[1] for c in steps) / len(steps)
    peaks = harness.peaks(readings.device_kind)
    floor = {"flops": flops / peaks["bf16_flops_per_s"],
             "bytes": nbytes / peaks["hbm_bytes_per_s"]}
    floor["max"] = max(floor.values())
    return 100.0 * floor[params["bound"]] / (seconds / executions)
