"""Pipeline engine integration: exactness vs the single-device reference,
fault injection -> reconfigure -> resume (loss continuity), migration
identity, and checkpoint-restart determinism."""
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.core.detector.detector import FailureReport
from repro.core.scheduler.plan import initial_plan
from repro.core.scheduler.repartition import costs_for_arch
from repro.core.scheduler.scheduler import Scheduler
from repro.data.synth import SyntheticPackedDataset
from repro.engine.pipeline import PipelineEngine
from repro.models.model import loss_fn, stacked_init
from repro.parallel.sharding import NULL_POLICY, split_annotations
from repro.train.optimizer import make_optimizer

# every test here compiles multi-stage jax pipelines (12-33 s apiece); the
# tier-1 suite covers the same scheduler/migration logic through the numpy
# simulator and golden tests
pytestmark = pytest.mark.slow

CFG = reduced(get_arch("qwen3-8b"), n_layers=4)
REPO = Path(__file__).resolve().parents[1]


def _batch(i=0, B=8, S=64):
    ds = SyntheticPackedDataset(CFG, S, B, seed=3)
    return {k: jnp.asarray(v) for k, v in ds.batch_at(i).items()}


def test_pipeline_matches_reference_loss():
    batch = _batch()
    params, _ = split_annotations(stacked_init(jax.random.PRNGKey(0), CFG))
    _, aux = loss_fn(CFG, params, batch, NULL_POLICY, use_scan=False, remat=False)
    eng = PipelineEngine(CFG, initial_plan(4, dp=2, pp=2, tp=1, microbatches=2),
                         optimizer=None, seed=0)
    loss, _ = eng.run_iteration(batch)
    assert abs(loss - float(aux["loss"])) < 2e-3


def test_migration_placement_identity():
    """Executing a micro-batch's stage on a peer replica (Fig. 6b) is
    mathematically identical — replicas are synchronized."""
    from repro.core.detector.dag_sim import ChunkId

    batch = _batch()
    plan = initial_plan(4, dp=2, pp=2, tp=1, microbatches=2)
    eng = PipelineEngine(CFG, plan, optimizer=None, seed=0)
    base, _ = eng.run_iteration(batch)
    placement = {
        ChunkId("F", 0, 1, 0): (1, 1),
        ChunkId("B", 0, 1, 0): (1, 1),
    }
    mig, _ = eng.run_iteration(batch, placement=placement)
    assert abs(base - mig) < 1e-5


def test_failstop_reconfigure_resume_loss_continuity():
    """Kill a device mid-training; Scheduler re-plans (TP exclusion +
    repartition); engine reshards; loss stays continuous (Fig. 12)."""
    opt = make_optimizer("adamw", lr=5e-3)
    plan = initial_plan(4, dp=2, pp=2, tp=2, microbatches=2)
    eng = PipelineEngine(CFG, plan, optimizer=opt, seed=0)
    losses = []
    for i in range(4):
        loss, _ = eng.run_iteration(_batch(i))
        losses.append(loss)
    # fail-stop device 5 (replica 1, stage 0)
    sch = Scheduler(layer_costs=costs_for_arch(CFG, 64))
    speeds = {d: 1.0 for d in plan.devices}
    speeds[5] = 0.0
    ad = sch.adapt(plan, speeds, failed={5})
    assert ad.plan.replicas[1].stages[0].tp == 1  # selective exclusion
    eng.apply_plan(ad.plan)
    for i in range(4, 8):
        loss, _ = eng.run_iteration(_batch(i))
        losses.append(loss)
    # continuity: the post-reconfig loss doesn't jump (same params, math)
    assert abs(losses[4] - losses[3]) < 0.15
    assert all(np.isfinite(losses))


def test_fault_tolerant_training_subprocess_8dev():
    """Full driver on 8 emulated host devices: inject a fail-stop, verify
    reconfiguration + completion (the multi-device integration test)."""
    code = (
        "import repro.launch.train as T; "
        "r = T.main(['--arch','qwen3-8b','--reduced','--mode','pipeline',"
        "'--dp','2','--pp','2','--tp','2','--steps','6','--seq-len','64',"
        "'--batch','8','--inject-failstop','3:5']); "
        "import numpy as np; assert np.isfinite(r['losses']).all(); "
        "assert r['reconfigs'] == [3], r['reconfigs']"
    )
    env = {"REPRO_HOST_DEVICES": "8", "PYTHONPATH": "src"}
    import os

    full_env = dict(os.environ)
    full_env.update(env)
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env=full_env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_checkpoint_restart_determinism(tmp_path):
    """Train 6 steps straight vs 3 steps + restart + 3 steps: identical
    final loss (resumable data pipeline + exact state restore)."""
    import os
    import subprocess
    import sys

    def run(steps, resume):
        code = (
            "import repro.launch.train as T; import json; "
            f"r = T.main(['--arch','qwen3-8b','--reduced','--mode','spmd',"
            f"'--steps','{steps}','--seq-len','64','--batch','4',"
            f"'--ckpt-dir','{tmp_path}','--ckpt-interval','3'"
            + (",'--resume'" if resume else "")
            + "]); print('FINAL', r['losses'][-1])"
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = "src"
        p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                           env=env, capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        return float(p.stdout.strip().split("FINAL")[-1])

    loss_straight = run(6, resume=False)
    import shutil

    shutil.rmtree(tmp_path)
    run(3, resume=False)  # writes ckpt at step 3
    loss_restart = run(6, resume=True)  # resumes from 3
    assert loss_restart == pytest.approx(loss_straight, abs=1e-5)
