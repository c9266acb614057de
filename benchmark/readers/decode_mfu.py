"""The whole decode step's share of the chip's peak: the operations
``counts.py`` gives for the traced steps, at the slots and context
lengths the client's records put in each, over the device time of the
step program's executions in the trace, over the bf16 peak.

params: ``program`` (regular expression on the step program's name).
"""


def read(readings, params):
    from benchmark import counts, harness
    from benchmark.readers import program_time

    ds = program_time.durations(readings, params["program"])
    steps = readings.client.get("traced_step_contexts") or []
    if not ds or not steps:
        return None
    flops = sum(counts.gpt2_decode_step_flops(readings.cell.config, ctx)
                for ctx in steps) / len(steps)
    seconds = sum(ds) / len(ds)
    peak = harness.peaks(readings.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / seconds / peak
