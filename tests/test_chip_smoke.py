"""chip_smoke.py on the CPU: every phase at tiny size on the XLA tier
(called directly, not through main), and the script's refusal to run
without a TPU or with a forcing knob set."""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def smoke(monkeypatch):
    """The script as a module, with fresh process registries so the
    no-fallback phase sees only what this test ran (the pallas_call
    recorder is undone at teardown)."""
    from jax.experimental import pallas as pl

    from ceph_tpu.ops import fallback, supervisor
    from ceph_tpu.telemetry import metrics

    monkeypatch.delenv("CEPH_TPU_ENGINE", raising=False)
    monkeypatch.delenv("CEPH_TPU_MESH", raising=False)
    monkeypatch.setattr(pl, "pallas_call", pl.pallas_call)
    prev_metrics = metrics.set_global_metrics(metrics.MetricsRegistry())
    prev_policy = fallback.set_global_policy(fallback.FallbackPolicy())
    prev_sup = supervisor.set_global_supervisor(None)
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    from ceph_tpu.telemetry import install_compile_monitor
    install_compile_monitor()
    try:
        yield mod
    finally:
        from ceph_tpu.parallel import plane
        plane.deactivate()
        metrics.set_global_metrics(prev_metrics)
        fallback.set_global_policy(prev_policy)
        supervisor.set_global_supervisor(prev_sup)


def test_smoke_phases_tiny_on_cpu(smoke):
    calls = smoke.watch_pallas_calls()
    dev = smoke.require_device("cpu", 1)
    assert dev["platform"] == "cpu"
    rs = smoke.encode_phase(8, 1 << 18, seed=3)
    # clay narrowed to k=4 m=2 d=5: the k=8 composite's XLA build takes
    # ~30 s to compile on the CPU (the TPU routes it to the MXU)
    rows = smoke.decode_phase(rs, 8, shec_size=6 * 65536,
                              clay_size=1 << 19, seed=3,
                              clay_profile={"k": "4", "m": "2", "d": "5"})
    assert set(rows) == {"rs_k8_m3_e2", "rs_k8_m3_e3",
                         "shec_k6_m3_c2_e1", "clay_k4_m2_d5_e1"}
    # clay single-chunk repair reads d helpers, shec fewer than k
    assert len(rows["clay_k4_m2_d5_e1"]["reads"]) == 5
    assert len(rows["shec_k6_m3_c2_e1"]["reads"]) < 6
    ups = smoke.crush_phase(64, rep_pgs=256, ec_pgs=64, oracle_pgs=64,
                            seed=3)
    assert {u.shape for u in ups.values()} == {(256, 3), (64, 11)}
    import jax
    assert not jax.config.jax_enable_x64    # bulk CRUSH's scope ended
    smoke.encode_phase(8, 1 << 18, seed=4, phase="encode_after_crush")
    out = smoke.no_fallback_phase("cpu", "xla", calls)
    assert out["ec_device_calls"] > 0
    assert out["pallas_calls"] == 0


def test_smoke_multichip_phase_on_virtual_devices(smoke):
    import jax
    n = jax.device_count()
    if n < 2:
        pytest.skip("needs virtual devices (tests/conftest.py)")
    assert smoke.multichip_phase(n, stripes=8, stripe_size=1 << 18,
                                 n_osds=64, rep_pgs=128, ec_pgs=32,
                                 seed=5) == {"devices": n}


def test_no_fallback_phase_catches_a_forced_tier(smoke):
    from ceph_tpu.ops.fallback import FallbackPolicy, set_global_policy
    set_global_policy(FallbackPolicy(force="numpy"))
    from ceph_tpu.ops.fallback import global_policy
    global_policy().engine("cpu")
    with pytest.raises(smoke.SmokeFailure, match="fallback tier"):
        smoke.no_fallback_phase("cpu", "xla", [])


@pytest.mark.parametrize("extra_env", [{}, {"CEPH_TPU_ENGINE": "xla"},
                                       {"CEPH_TPU_MESH": "auto"}],
                         ids=["cpu-backend", "engine-forced",
                              "mesh-forced"])
def test_smoke_script_refuses_without_tpu(extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra_env)
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "FAILED" in r.stderr
