"""``chip_smoke.py``'s phases at a tiny size on the CPU test mesh, and the
command itself refusing to run without a TPU.

The chip runs the same functions at GPT-2-small / ResNet-50 width; here
they prove their own control flow and checks (``kernels=False``: on the
CPU the lowered programs hold no Pallas TPU kernel to look for)."""

import dataclasses
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    lm=dict(vocab_size=64, d_model=32, heads=2, depth=1, d_ff=64, max_len=64),
    slots=4, cache_len=64, decode_block=4,
    prompt_lens=(20, 3, 9), late=1, new_tokens=6,
    image=32, images=8, stage_batch=4, landmark_rows=8, landmark_batch=4,
    train_batch=4, train_seq=16, train_steps=3,
)


@pytest.fixture(scope="module")
def log():
    return chip_smoke.CompileLog()


def test_serve_phase(log):
    out = chip_smoke.serve_phase(TINY, 0, log, kernels=False)
    # on the CPU the engine is bit-identical to generate()
    assert out["bf16_dense_pool"]["tokens_identical_to_generate"] == "18/18"
    assert out["bf16_dense_pool"]["worst_scaled_logit_gap"] <= 1e-3
    assert out["int8_paged_pool"]["page_size"] == 8
    behind = out["bf16_dense_pool_async_host"]
    assert behind["flip_rate_vs_sync"] == 0.0
    assert behind["overlapped_dispatches"] > 0
    assert behind["pool_write_dispatches"] == [1]
    assert out["bf16_dense_pool"]["compiles"] > 0


def test_stage_phase(log):
    out = chip_smoke.stage_phase(TINY, 0, log)
    assert out["rows"] == 8 and out["smoke_images_per_sec_second_call"] > 0


def test_train_phase(log):
    out = chip_smoke.train_phase(TINY, 0, log, kernels=False)
    assert len(out["losses"]) == 3 and out["kernel_calls"] == 0


def test_timing_phase(log):
    out = chip_smoke.timing_phase(TINY, 0, log)
    assert out["ms_to_block_until_ready"] > 0
    assert out["ms_to_host_fetch_of_scalar"] > 0


def test_multichip_phase(log):
    sz = dataclasses.replace(TINY, prompt_lens=(20, 3), late=1)
    out = chip_smoke.multichip_phase(sz, 0, log, kernels=False)
    # on virtual CPU devices the mesh engine is bit-identical
    assert out["tokens_identical_to_one_device"] == "12/12"
    assert len(out["params_bytes_per_device"]) == 4
    assert set(out["train_losses"]) == {"data=1", "data=4"}


def test_a_failed_check_raises():
    with pytest.raises(chip_smoke.SmokeFailure, match="went wrong"):
        chip_smoke.check(False, "went wrong")


def test_kernel_check_fails_where_no_kernel_was_lowered(log):
    """On the CPU the engine lowers its kernels' interpreter: the check
    that the chip run relies on must notice."""
    graph, variables = chip_smoke.build_lm(TINY, 0)
    prompts = chip_smoke.make_prompts(TINY, 0)[:2]
    engine, results = chip_smoke.drive(graph, variables, prompts, TINY)
    with pytest.raises(chip_smoke.SmokeFailure, match="kernel calls"):
        chip_smoke.check_clean_run(engine, results, True, "cpu")


def test_command_exits_nonzero_without_a_tpu():
    """No accelerator: another exit code than 0, no result line, no phase."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 2
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr
