"""Non-RT request support (paper §3.3) and launcher end-to-end drills."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Category, DeepRT, ExecutionModel, ProfileTable, Request


def make_table():
    t = ProfileTable()
    for b in [1, 2, 4, 8, 16, 32]:
        t.record("m", (3, 224, 224), b, 0.004 + 0.0015 * b)
    return t


class TestNonRealtime:
    def test_nonrt_never_causes_rt_miss(self):
        """Paper §3.3: non-RT requests batch under a large window with a
        background-server guard — RT deadlines stay intact even when
        non-RT load is heavy."""
        table = make_table()
        sched = DeepRT(table, execution=ExecutionModel(actual_fn=lambda j, w: w))
        rt = Category("m", (3, 224, 224), realtime=True)
        nrt = Category("m", (3, 224, 224), realtime=False)
        r_rt = Request(category=rt, period=0.05, relative_deadline=0.2, n_frames=60)
        assert sched.submit_request(r_rt).admitted
        # Heavy non-RT stream (bypasses admission by design).
        for _ in range(3):
            r = Request(category=nrt, period=0.001, relative_deadline=9.0, n_frames=50)
            res = sched.submit_request(r)
            assert res.admitted and res.phase == 0
        m = sched.run()
        rt_missed = [
            k for k, (a, d, c) in m.frame_records.items()
            if k[0] == r_rt.request_id and c > d + 1e-9
        ]
        assert not rt_missed, f"non-RT load caused RT misses: {rt_missed}"

    def test_nonrt_work_completes_in_slack(self):
        table = make_table()
        sched = DeepRT(table)
        nrt = Category("m", (3, 224, 224), realtime=False)
        r = Request(category=nrt, period=0.01, relative_deadline=5.0, n_frames=10)
        sched.submit_request(r)
        m = sched.run()
        assert m.completed_frames == 10

    def test_nonrt_batch_cap_bounds_jobs(self):
        from repro.core.scheduler import NONRT_BATCH_CAP

        table = make_table()
        sched = DeepRT(table)
        nrt = Category("m", (3, 224, 224), realtime=False)
        r = Request(category=nrt, period=0.001, relative_deadline=9.0, n_frames=64)
        sched.submit_request(r)
        sched.run()
        assert max(
            j.batch_size for j in sched.worker.completed_jobs
        ) <= max(NONRT_BATCH_CAP, 1)


@pytest.mark.slow
class TestLaunchers:
    def test_train_launcher_with_crash_resume(self, tmp_path):
        """Full fault-tolerance drill through the real CLI: train, crash,
        resume from checkpoint, finish."""
        base = [
            sys.executable, "-m", "repro.launch.train",
            "--arch", "granite-3-2b", "--tiny",
            "--steps", "12", "--batch", "2", "--seq", "32",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "5",
        ]
        env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
        import os

        env.update({k: v for k, v in os.environ.items() if k not in env})
        env["PYTHONPATH"] = "src"
        r1 = subprocess.run(
            base + ["--fail-at", "7"], capture_output=True, text=True, env=env,
            cwd="/root/repo", timeout=600,
        )
        assert "simulated failure" in (r1.stdout + r1.stderr)
        r2 = subprocess.run(
            base, capture_output=True, text=True, env=env, cwd="/root/repo",
            timeout=600,
        )
        assert r2.returncode == 0, r2.stderr[-2000:]
        assert "resuming from checkpoint step 5" in r2.stdout
        assert "step   11" in r2.stdout


# ---------------------------------------------------------------------------
# The serving launcher's path (what chip_smoke.py runs on the chip), at the
# tiny preset on the CPU, plus per-slice device placement.
# ---------------------------------------------------------------------------

REPO = Path(__file__).resolve().parents[1]
SMALL = dict(prefill_seq=16, decode_seq=64, decode_period=0.05,
             decode_frames=12, prefill_period=0.1, prefill_frames=4)


def _cpu_env(**extra):
    env = dict(os.environ)
    env.update(PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu", **extra)
    return env


class TestServeLauncher:
    @pytest.fixture(scope="class")
    def served(self):
        from repro.configs.registry import tiny
        from repro.launch.serve import Traffic, build, serve

        stack = build(tiny("granite-3-2b"), Traffic(**SMALL), profile_runs=2)
        return stack, serve(stack)

    def test_serves_end_to_end_conserved(self, served):
        from repro.launch.serve import conserved

        _stack, out = served
        c = out.counts
        assert c["admitted"] == c["streams"] == 6
        assert c["completed"] > 0
        assert conserved(c), c
        # A clean link: every sent frame ingested exactly once, even when
        # a lagging host rejects some as late at the door.
        assert c["ingested"] == c["frames_sent"], c
        assert c["lost"] == 0 and c["health_transitions"] == 0
        assert c["wire_conserved"] == 1 and c["malformed"] == 0

    def test_zero_recompiles_in_served_window(self, served):
        _stack, out = served
        assert out.counts["decode_compiles"] == 0
        assert out.counts["prefill_compiles"] == 0

    def test_logit_checks_pass_on_served_engine(self, served):
        """chip_smoke's two comparisons, on the engine that just served:
        an arena row vs a batch-1 reference, and the Pallas decode kernel
        (interpret mode here) vs the XLA path."""
        from repro.launch import checks

        stack, _out = served
        (sl,) = stack.slices.values()
        cfg, seq = stack.cfg, stack.traffic.decode_seq
        got = checks.arena_row_logits(sl.engine, cfg.arch_id, seq, 17)
        ref = checks.reference_logits(sl.engine, cfg.arch_id, seq, 17)
        rtol = checks.LOGIT_RTOL[cfg.param_dtype]
        assert checks.compare_logits(got, ref, rtol)["ok"]
        kern = checks.pallas_vs_xla(sl.engine, cfg.arch_id, seq)
        assert kern["attention"]["ok"] and kern["step"]["ok"], kern

    def test_compare_logits_catches_wrong_row(self):
        from repro.launch.checks import compare_logits

        ref = np.array([[0.1, 0.9, -0.3]], np.float32)
        assert compare_logits(ref + 1e-7, ref, 1e-5)["ok"]
        assert not compare_logits(ref[:, ::-1], ref, 1e-5)["ok"]
        assert not compare_logits(ref * np.nan, ref, 1e-5)["ok"]
        # Every logit within the band, but the argmax moved to a token
        # the reference ranks more than the band below its maximum.
        one_hot = np.array([[0.0, 1.0, 0.0]], np.float32)
        moved = np.array([[0.55, 0.45, 0.0]], np.float32)
        assert not compare_logits(moved, one_hot, 0.6)["ok"]


class TestDevicePlacement:
    def test_engine_commits_params_arena_and_staging(self):
        import jax

        from repro.configs.registry import tiny
        from repro.launch.checks import placed_on
        from repro.serving.engine import InferenceEngine

        dev = jax.devices()[0]
        mid = "granite-3-2b"
        eng = InferenceEngine({mid: tiny(mid)}, max_slots=4, device=dev)
        arena = eng.arena(mid, 16)
        staged = eng.staging_ring("decode", mid, 16, 4).stage_rows(None, 0)
        for x in jax.tree.leaves([eng.params, arena.cache, arena.cur,
                                  arena.active, staged]):
            assert x.committed and x.devices() == {dev}
        assert placed_on([eng.params, arena.cache], dev)

    def test_cluster_maps_slice_i_to_device_i_mod_n(self):
        """Four slices over three (virtual CPU) devices: slice i runs on
        jax.devices()[i % 3], params and arena committed there."""
        code = (
            "import jax\n"
            "from repro.configs.registry import tiny\n"
            "from repro.launch.checks import placed_on\n"
            "from repro.serving.batcher_bridge import build_live_cluster\n"
            "m = 'granite-3-2b'\n"
            "_, slices = build_live_cluster({m: tiny(m)}, [(m, (8,), 'decode')],\n"
            "    slice_names=('a', 'b', 'c', 'd'), batch_sizes=(1,),\n"
            "    profile_runs=1, nonrt_cap=1)\n"
            "devs = jax.devices()\n"
            "for i, sl in enumerate(slices.values()):\n"
            "    e = sl.engine\n"
            "    assert e.device == devs[i % 3], (i, e.device)\n"
            "    assert placed_on([e.params, e.arena(m, 8).cache], devs[i % 3])\n"
            "print('placed', [sl.engine.device.id for sl in slices.values()])\n"
        )
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=3"),
            cwd=str(REPO), timeout=300,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        assert "placed [0, 1, 2, 0]" in r.stdout


class TestCompileCache:
    """Each case in a fresh process: the cache settings are process-wide."""

    CODE = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.compile_cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()\n"
    )

    def _run(self, code, **env):
        env = _cpu_env(**env)
        if not env.get("JAX_COMPILATION_CACHE_DIR"):  # "" = unset
            env.pop("JAX_COMPILATION_CACHE_DIR", None)
        r = subprocess.run(
            [sys.executable, "-c", code], capture_output=True,
            text=True, env=env, cwd=str(REPO), timeout=120,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        return r.stdout.split()

    def test_env_var_places_the_cache(self, tmp_path):
        returned, configured = self._run(
            self.CODE, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
            JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
            JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
        )
        assert returned == configured == str(tmp_path)
        assert any(p.name.endswith("-cache") for p in tmp_path.iterdir())

    def test_default_is_fixed_path_in_checkout(self):
        # Only the setting: nothing is compiled into the checkout's cache.
        returned, configured = self._run(
            self.CODE.rsplit("jax.jit", 1)[0], JAX_COMPILATION_CACHE_DIR=""
        )
        assert returned == configured == str(REPO / ".jax_cache")
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


class TestChipSmokeRefusesCpu:
    def test_no_tpu_exits_nonzero_without_ok_line(self):
        r = subprocess.run(
            [sys.executable, str(REPO / "chip_smoke.py")], capture_output=True,
            text=True, env=_cpu_env(), cwd=str(REPO), timeout=120,
        )
        assert r.returncode != 0
        assert '"ok"' not in r.stdout

    def test_alone_without_repo_exits_nonzero(self, tmp_path):
        shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
        env = _cpu_env()
        env.pop("PYTHONPATH")
        r = subprocess.run(
            [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
            env=env, cwd=str(tmp_path), timeout=120,
        )
        assert r.returncode != 0
        assert '"ok"' not in r.stdout
