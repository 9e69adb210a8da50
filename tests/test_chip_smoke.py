"""CPU rehearsal of ``chip_smoke.py``: its phases at a reduced size.

The script itself refuses to run without a TPU; its phase functions take
a config, so here they run ``reduced(minicpm-2b)`` on the CPU — the
one-chip phases in process, the four-chip phases in a subprocess with
four virtual CPU devices.  Every later change to the serving engine, the
DP train step or the transport kernels is rehearsed for free.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

_ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_ROOT))

import chip_smoke  # noqa: E402

SERVE_KW = dict(
    num_slots=4, max_len=48, gen_len=5, prompt_lens=(3, 9, 5, 12),
    buckets=(8, 16),
)


@pytest.fixture(scope="module")
def cfg():
    from repro.configs import get_config, reduced

    return reduced(get_config(chip_smoke.ARCH))


def test_serve_phase_agrees_with_serial_and_serve_batch(cfg):
    r = chip_smoke.serve_phase(cfg, seed=0, **SERVE_KW)
    # f32 on the CPU: the two programs agree bitwise, so every engine
    # token is the plain step's argmax
    assert r["continuous_eq_serial"] and r["serial_eq_serve_batch"]
    assert r["max_greedy_margin"] == 0.0
    assert r["dispatch"] == {}  # no mesh: no decode collectives
    assert min(r["fault_margin"].values()) > chip_smoke.GREEDY_MARGIN_TOL


def test_serve_phase_releases_device_memory_without_gc(cfg):
    # the one-chip run trains right after serving: whatever the serve
    # phase leaves alive (until the cyclic collector runs) is taken from
    # the train phase's device memory
    import gc

    gc.collect()
    before = chip_smoke.live_bytes()
    gc.disable()
    try:
        chip_smoke.serve_phase(cfg, seed=0, **SERVE_KW)
        assert chip_smoke.live_bytes() == before
    finally:
        gc.enable()


def test_serve_check_catches_an_off_by_one_prefill(cfg, monkeypatch):
    # plant a fault the serial run shares: the engine prefills all but
    # the last prompt token, so its cache index is one short
    from repro.serve.engine import ServeEngine

    prefill = ServeEngine._prefill

    def short_prefill(self, req):
        full = req.prompt
        req.prompt = full[:-1]
        try:
            return prefill(self, req)
        finally:
            req.prompt = full

    monkeypatch.setattr(ServeEngine, "_prefill", short_prefill)
    with pytest.raises(chip_smoke.SmokeFailure, match="not greedy"):
        chip_smoke.serve_phase(cfg, seed=0, **SERVE_KW)


def _train_one_device(cfg, bits):
    from repro.core import comm
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), chip_smoke.MESH_AXES)
    return chip_smoke.train_phase(
        cfg, mesh=mesh, seed=0, batch=2, seq=32,
        sync_cfg=comm.CommPolicy(compress_bits=bits),
    )


@pytest.mark.parametrize("bits", [None, 8])
def test_train_phase_on_one_device_mesh(cfg, bits):
    r = _train_one_device(cfg, bits)
    assert len(r["losses"]) == 4 and r["state_devices"] == [1]
    # step 0 runs at lr 0 (warmup from 0): the batch's loss is unchanged
    assert r["losses"][1] == r["losses"][0]


def test_int8_loss_drop_close_to_uncompressed(cfg):
    ref, got = _train_one_device(cfg, None), _train_one_device(cfg, 8)
    chip_smoke.require_compressed_close(ref, got, "int8")


def test_train_check_rejects_a_rising_loss(cfg, monkeypatch):
    # Adam's sign-like first steps overshoot at a large lr; the phase
    # must fail rather than report finite losses as a pass
    monkeypatch.setattr(chip_smoke, "TRAIN_LR", 3.0)
    with pytest.raises(chip_smoke.SmokeFailure, match="does not fall"):
        _train_one_device(cfg, None)


def test_four_chip_phases_on_virtual_devices(cfg):
    code = f"""
import os, json, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, {str(_ROOT)!r})
import chip_smoke
from repro.configs import get_config, reduced
from repro.launch.mesh import make_mesh
cfg = reduced(get_config(chip_smoke.ARCH))
mesh = make_mesh((2, 2), chip_smoke.MESH_AXES)
r = chip_smoke.four_chip_phases(
    cfg, mesh, seed=0, train_cfg=cfg, batch=2, seq=32,
    serve_kw={SERVE_KW!r},
)
print("RESULT:" + json.dumps({{
    "engines": sorted({{a["engine"] for a in r["allreduce"]}}),
    "train": sorted(r["train"]),
    "logits_engine": r["serve"]["dispatch"]["logits_allreduce"]["engine"],
    "params_devices": r["serve"]["params_devices"],
}}))
"""
    env = dict(os.environ, PYTHONPATH=str(_ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout[-4000:]
    r = json.loads(line[-1][len("RESULT:"):])
    assert r["engines"] == ["mla", "mla_pipelined", "nap", "psum"]
    assert r["train"] == ["int4_ef", "int8", "nap", "psum"]
    assert r["params_devices"] == 4


def test_script_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(_ROOT / "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
