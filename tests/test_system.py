"""End-to-end behaviour tests for the paper's system."""
import subprocess
import sys
import os

from repro.configs import all_pairs, get_config, lowering_plan
from repro.models.config import INPUT_SHAPES


def test_all_pairs_enumerated():
    pairs = list(all_pairs())
    assert len(pairs) == 40                      # 10 archs x 4 shapes
    skips = [p for p in pairs if lowering_plan(*p).skip]
    assert [(a, s) for a, s in skips] == [("whisper-tiny", "long_500k")]


def test_lowering_plans_consistent():
    for arch, shape in all_pairs():
        lp = lowering_plan(arch, shape)
        if lp.skip:
            continue
        assert lp.mode == INPUT_SHAPES[shape].mode
        if shape == "long_500k":
            # sub-quadratic requirement: native recurrent or windowed
            native = arch in ("recurrentgemma-2b", "xlstm-125m")
            assert native or lp.window_override == 8192, (arch, lp)
            assert lp.cache_len <= 8192
        if lp.mode == "decode" and lp.fsdp == 1:
            # serve-mode residency only when TP-local weights fit
            assert get_config(arch).param_count() * 2 / 16 <= 8e9


def test_paper_policy_matches_paper_setup():
    """Paper Setup section: g128 for INT8/6/5, g32 for INT4/3/2, SR at
    INT2; dispatch-only A2A quantization."""
    from repro.core.comm_config import default_comm_config
    for bits, g, spike in [(8, 128, False), (6, 128, False),
                           (5, 128, False), (4, 32, False),
                           (3, 32, False), (2, 32, True)]:
        cfg = default_comm_config(bits)
        assert (cfg.group, cfg.spike) == (g, spike), bits


def test_train_launcher_cli(tmp_path):
    """The real CLI end-to-end: 3 steps of a reduced arch + checkpoint."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    ck = str(tmp_path / "ck.npz")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "xlstm-125m",
         "--smoke", "--steps", "3", "--seq", "32", "--batch", "2",
         "--ckpt", ck, "--log-every", "1"],
        capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert os.path.exists(ck)
    assert "loss" in r.stdout


def test_serve_run_matches_main():
    """``serve.run`` on a config built by the caller gives the tokens
    ``serve.main`` gives for the same arguments (same process, so the
    same seeded weights)."""
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.launch import serve
    argv = ["--arch", "qwen3-14b", "--smoke", "--batch", "2",
            "--prompt-len", "8", "--gen", "3", "--codec-backend", "pallas"]
    via_main = serve.main(argv)
    via_run = serve.run(get_smoke_config("qwen3-14b"),
                        serve.parse_args(argv))
    np.testing.assert_array_equal(via_run["first"], via_main["first"])
    np.testing.assert_array_equal(via_run["generated"],
                                  via_main["generated"])
    assert via_run["generated"].shape == (2, 3)
