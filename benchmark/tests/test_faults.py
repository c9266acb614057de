"""A run with the timed path broken underneath has to come out with
``correct`` false. Each test skips the harness's look for a chip, drives
the rest of a run (driver, window, reference, comparison, result line)
at the cell's rehearsal size, and reads the result line.

Faults a cell here can have: half of the batch left out; the exchange
between chips left out (four-chip cell); an answer or a token altered
where it is produced. No cell trains, so "a step that returns its state
unchanged" has no place. The unbroken run comes first: it has to read
``correct`` true, or the broken ones prove nothing.
"""

import argparse
import json
import time

import numpy as np
import pytest

FRAME_1 = "inception-v3.device-frame"
FRAME_4 = "inception-v3.device-frame-4chip"
SERVE = "gpt2-small.closed-loop-384"


def drive(name, capsys, seed=7, seconds=1.0):
    import jax

    from benchmark import harness

    cell = harness.load_cell(name, rehearsal=True)
    devices = jax.devices()[:cell.chips]
    if len(devices) < cell.chips:
        pytest.skip(f"{name} needs {cell.chips} devices")
    driver = harness.driver_of(cell)
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0)
    driver.run(cell, args, time.perf_counter(), devices)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL ")
    return json.loads(last[len("REHEARSAL "):])


def frame_subset(monkeypatch, pick):
    """map_blocks sees only the rows ``pick`` keeps of every block."""
    import tensorframes_tpu as tfs
    from tensorframes_tpu.frame import TensorFrame

    real = tfs.map_blocks

    def broken(fetches, frame, *a, **kw):
        if not frame.is_materialized:
            return real(fetches, frame, *a, **kw)
        blocks = pick(frame.blocks())
        cut = TensorFrame(blocks, frame.schema)
        cut._mesh, cut._axis = frame.mesh, frame._axis
        return real(fetches, cut, *a, **kw)

    monkeypatch.setattr(tfs, "map_blocks", broken)


@pytest.mark.parametrize("name", [FRAME_1, FRAME_4, SERVE])
def test_unbroken_run_is_correct(name, capsys):
    line = drive(name, capsys)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"


@pytest.mark.parametrize("name", [FRAME_1, FRAME_4])
def test_half_of_the_batch_left_out(name, capsys, monkeypatch):
    frame_subset(monkeypatch, lambda blocks: blocks[:len(blocks) // 2])
    line = drive(name, capsys)
    assert line["correct"] is False
    assert line["checks"]["class_sum_gap"]["value"] > \
        line["checks"]["class_sum_gap"]["limit"]


def test_exchange_between_chips_left_out(capsys, monkeypatch):
    """Every chip sums its own rows and nothing combines them: the host
    gets one chip's partial sums."""
    import jax

    def one_chip_only(blocks):
        out = []
        for b in blocks:
            x = b["images"]
            mine = x.shape[0] // len(x.sharding.device_set)
            kept = np.zeros(x.shape, x.dtype)
            # the other chips' rows contribute nothing to what arrives
            kept[:mine] = np.asarray(x)[:mine]
            out.append({"images": jax.device_put(kept, x.sharding)})
        return out

    frame_subset(monkeypatch, one_chip_only)
    line = drive(FRAME_4, capsys)
    assert line["correct"] is False
    assert line["checks"]["class_sum_gap"]["value"] > \
        line["checks"]["class_sum_gap"]["limit"]


@pytest.mark.parametrize("which", ["every_pass", "first_pass_only"])
def test_an_answer_altered_where_it_is_produced(which, capsys, monkeypatch):
    import tensorframes_tpu as tfs

    real = tfs.reduce_blocks
    calls = {"n": 0}

    def broken(fetches, frame, *a, **kw):
        out = real(fetches, frame, *a, **kw)
        calls["n"] += 1
        # warm passes are calls 1 and 2; the window starts at call 3
        if which == "every_pass" or calls["n"] == 3:
            # reduce_blocks hands one fetch back bare, several as a dict
            bare = not isinstance(out, dict)
            v = np.array(out if bare else out["logits"])
            v[[0, 1]] = v[[1, 0]]  # two classes' sums change places
            out = v if bare else {**out, "logits": v}
        return out

    monkeypatch.setattr(tfs, "reduce_blocks", broken)
    line = drive(FRAME_1, capsys)
    assert line["correct"] is False
    failing = "class_sum_gap" if which == "every_pass" else "pass_drift"
    assert line["checks"][failing]["value"] > line["checks"][failing]["limit"]


def test_a_token_altered_where_it_is_produced(capsys, monkeypatch):
    """Every third decode step hands back shifted token ids."""
    from tensorframes_tpu.serving.decode import DecodeEngine

    real = DecodeEngine._run_step
    calls = {"n": 0}

    def broken(self, *args):
        pool, nxt = real(self, *args)
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            nxt = (np.asarray(nxt) + 1) % int(self.cfg.vocab_size)
        return pool, nxt

    monkeypatch.setattr(DecodeEngine, "_run_step", broken)
    line = drive(SERVE, capsys, seconds=2.0)
    assert line["correct"] is False
    assert line["checks"]["served_logit_gap"]["value"] > \
        line["checks"]["served_logit_gap"]["limit"]
