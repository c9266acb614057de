"""The whole block step's share of the chips' peak: the operations
``counts.py`` gives for one block of the frame, over the block
program's median device time, over chips x the bf16 peak. The block
program runs on every chip at once, each on its rows.

params: ``program`` (regular expression on the program's name).
"""


def read(readings, params):
    from benchmark import counts, harness
    from benchmark.readers import program_time

    ds = program_time.durations(readings, params["program"])
    if not ds:
        return None
    cell = readings.cell
    flops = counts.inception_v3_flops(cell.config,
                                      int(cell.traffic["block_rows"]))
    peak = harness.peaks(readings.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / harness.quantile(ds, 0.5) / (cell.chips * peak)
