"""chip_smoke.py off the chip: its phase functions rehearsed at a tiny width
on the CPU backend through a real cluster (a TPU resource that is only a
number), and the script itself refusing to pass without a TPU."""

import os
import subprocess
import sys

import pytest

import cluster_anywhere_tpu as ca

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = dict(d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_head=16, d_ff=128)


@pytest.fixture(scope="module")
def one_fake_chip():
    if ca.is_initialized():
        ca.shutdown()
    ca.init(num_cpus=4, num_tpus=1)
    yield
    ca.shutdown()


def test_mesh_phase_rehearsal():
    """--chips 4 on virtual devices: one worker that asked for four chips runs
    the fsdp x tp and dp steps against one device."""
    if ca.is_initialized():
        ca.shutdown()
    ca.init(num_cpus=4, num_tpus=4)
    try:
        cfg = dict(
            chip_smoke.TRAIN, widths=dict(TINY, vocab_size=128, max_seq_len=32),
            batch=8, seq=32, steps=3, learning_rate=1e-2, meshes=chip_smoke.MESHES,
        )
        rep = chip_smoke.phase_mesh(cfg)
    finally:
        ca.shutdown()
    assert rep["visible_chips"] == "0,1,2,3"
    assert set(rep["meshes"]) == {"single", "fsdp2_tp2", "dp4"}
    with pytest.raises(RuntimeError, match="tpu_custom_call"):
        chip_smoke.require_kernel(rep)


def test_train_phase_rehearsal(one_fake_chip):
    cfg = dict(
        chip_smoke.TRAIN, widths=dict(TINY, vocab_size=128, max_seq_len=32),
        batch=2, seq=32, steps=3, learning_rate=1e-2,
    )
    rep = chip_smoke.phase_train(cfg)
    (rec,) = rep["meshes"].values()
    assert len(rec["losses"]) == 3 and rec["compile_s"] > 0
    # every TPU worker points its compile cache at the one fixed place
    assert rep["cache_dir"] == os.path.join(ROOT, ".jax_cache")
    # a run that landed on the CPU is a failure, never a result
    with pytest.raises(RuntimeError, match="need 1 tpu"):
        chip_smoke.require_tpu(rep, 1)
    with pytest.raises(RuntimeError, match="tpu_custom_call"):
        chip_smoke.require_kernel(rep)
    with pytest.raises(RuntimeError, match="did not fall"):
        chip_smoke.check_train(dict(rec, losses=[1.0, 1.0]))


def test_serve_phase_rehearsal(one_fake_chip):
    rep = chip_smoke.phase_serve(dict(chip_smoke.SERVE, widths=TINY))
    assert rep["platform"] == "cpu" and rep["device_count"] == 1
    with pytest.raises(RuntimeError, match="need 1 tpu"):
        chip_smoke.require_tpu(rep, 1)


def test_script_fails_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("CA_NUM_TPUS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "need 1 TPU chip(s)" in proc.stderr
