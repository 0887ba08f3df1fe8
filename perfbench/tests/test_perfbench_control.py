"""`correct` fails where it should. With the served path broken
underneath (a token altered where it is produced, or a step that returns
its KV state unchanged) a whole run reads `correct` false; and the
control, the reference one precision step down, reads far above the
served path at the same positions."""
import numpy as np
import pytest

import check
import controls
import cpu_cell


@pytest.mark.parametrize("fault", ["token", "stale_state"])
def test_broken_served_path_is_not_correct(tmp_path, fault):
    _, cell = cpu_cell.make_copy(str(tmp_path))
    res, _ = cpu_cell.run(str(tmp_path), cell, seconds=1.5, fault=fault)
    assert res["correct"] is False
    assert res["check"]["max_gap"]["value"] > res["check"]["max_gap"][
        "limit"]


def _greedy(cfg, seed, prompt, n):
    """n tokens the reference itself picks greedily after `prompt`."""
    from families import dense

    seq = list(prompt)
    width = len(prompt) + n
    for _ in range(n):
        s = np.zeros(width, np.int32)       # one padded shape, one compile
        s[:len(seq)] = seq
        h = dense.hidden_states(seed, cfg, [s])[0]
        lg = np.asarray(dense.logits(seed, cfg, h))
        seq.append(int(lg[len(seq) - 1].argmax()))
    return np.asarray(seq[len(prompt):], np.int32)


def test_control_reads_above_served_path():
    _control_reads_above(cpu_cell.cfg())


def test_control_reads_above_served_path_quant():
    """The quantization-only plan in bfloat16, as `phi3m-w4quant` runs
    it, so the control is float8_e4m3fn."""
    _control_reads_above({**cpu_cell.cfg(cpu_cell.TINY_QUANT),
                          "dtype": "bfloat16"})


def _control_reads_above(cfg):
    rng = np.random.default_rng(0)
    items = []
    for n_prompt in (24, 24):
        p = rng.integers(1, cfg["vocab_size"], n_prompt).astype(np.int32)
        items.append((p, _greedy(cfg, 11, p, 6)))
    got = check.readings(11, cfg, items, controls.LOWER[cfg["dtype"]])
    assert got["served"]["max_gap"] == 0.0
    assert got["served"]["tokens"] == 12
    assert got["lower"]["tokens"] == 12
    assert got["lower"]["max_gap"] > 0.0
    limits = {"max_gap": {"limit": got["lower"]["max_gap"] / 2}}
    assert check.judge(limits, got["served"])[1] is True
    compared, ok = check.judge(limits, got["lower"])
    assert ok is False
    assert compared["max_gap"]["value"] == got["lower"]["max_gap"]
