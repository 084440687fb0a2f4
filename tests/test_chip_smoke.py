"""chip_smoke.py's phases at tiny sizes, interpret mode, on the CPU mesh.

This keeps the script from rotting between chip runs.  It is NOT a chip
pass: it prints ``platform=cpu``, compiles no kernel, and a number it prints
says nothing about a device.
"""

import sys
from pathlib import Path

import jax

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import chip_smoke  # noqa: E402


def test_tiny_lane_runs_every_phase_on_cpu(capsys):
    print(f"platform={jax.devices()[0].platform} — tiny sizes, interpret "
          f"mode: not a chip pass")
    chip_smoke.run_phases(chip_smoke.TINY, compiled=False)
    out = capsys.readouterr().out
    assert "platform=cpu" in out
    for phase in ("[kernels] compiled=False", "[trainer] one device",
                  "[server]", "[trainer] mesh {'dp': 4}",
                  "[trainer] mesh {'dp': 2, 'tp': 2}"):
        assert phase in out, out
    assert "FAIL" not in out and '"ok"' not in out


def test_main_fails_without_a_tpu_and_prints_no_result(capsys):
    assert chip_smoke.main() != 0
    cap = capsys.readouterr()
    assert "platform=cpu" in cap.out and '"ok"' not in cap.out
    assert "needs a TPU" in cap.err


def test_full_sizes_are_gpt2_small():
    f = chip_smoke.FULL
    assert (f.vocab, f.hidden, f.layers, f.heads, f.ffn, f.seq, f.batch) == (
        50304, 768, 12, 12, 3072, 1024, 16)
    assert f.steps >= 5 and f.slots == 8 and f.topk == (16384, 64, 8)
    assert ((16, 12, 1024, 64), (16, 12, 1024, 64)) in f.flash_shapes
