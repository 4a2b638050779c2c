"""The plain reference against the program, in float32 on the CPU at a tiny
qwen3 shape: the loss and its gradients, and one whole AdamW step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.archs import qwen3 as arch_mod
from chipbench.reference import qwen3 as ref
from chipbench.runners import common
from chipbench.tests import tiny


@pytest.fixture(scope="module")
def setup():
    cell = tiny.cell()
    cfg = cell.cfg
    traffic = common.traffic_for(cfg, cell.mix, seed=3)
    batch = {k: jnp.asarray(v) for k, v in traffic.batch(0).items()}
    params = ref.init_params(cfg, ref.key_for_seed(3))
    return cfg, batch, params


def test_the_adapter_gives_the_programs_own_tree(setup):
    from repro.models.model import stacked_init
    from repro.parallel.sharding import split_annotations

    cfg, _, params = setup
    arch = arch_mod.arch(cfg)
    theirs, _ = split_annotations(jax.eval_shape(lambda k: stacked_init(k, arch),
                                                 jax.random.PRNGKey(0)))
    ours = arch_mod.to_program(params)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype
    back = arch_mod.from_program(ours)
    assert sorted(back) == sorted(params)
    for k in params:
        np.testing.assert_array_equal(back[k], params[k])


@pytest.mark.parametrize("flash_chunk", [64, 16])  # dense path, chunked path
def test_loss_and_gradients_match_the_programs_loss_fn_in_float32(setup, flash_chunk):
    from repro.models.model import loss_fn
    from repro.parallel.sharding import NULL_POLICY

    cfg, batch, params = setup
    arch = arch_mod.arch(cfg)
    mb = {k: v[:1] for k, v in batch.items()}

    def theirs(p):
        return loss_fn(arch, arch_mod.to_program(p), mb, NULL_POLICY, use_scan=True,
                       remat=False, flash_chunk=flash_chunk, compute_dtype=jnp.float32)[0]

    def ours(p):
        nll, lse2, n = ref.nll_terms(cfg, p, mb, "f32")
        return nll / n + cfg["train"]["zloss"] * lse2 / n

    with jax.default_matmul_precision("highest"):
        la, ga = jax.value_and_grad(theirs)(params)
        lb, gb = jax.value_and_grad(ours)(params)
    np.testing.assert_allclose(float(la), float(lb), rtol=1e-5)
    for k in params:
        scale = float(jnp.max(jnp.abs(gb[k]))) + 1e-12
        np.testing.assert_allclose(np.asarray(ga[k]) / scale, np.asarray(gb[k]) / scale,
                                   atol=2e-5, err_msg=k)


def test_one_adamw_step_matches_the_programs_optimizer(setup):
    from repro.models.model import loss_fn
    from repro.parallel.sharding import NULL_POLICY
    from repro.train.train_step import global_norm

    cfg, batch, params = setup
    arch, tr = arch_mod.arch(cfg), cfg["train"]
    opt = common.optimizer(cfg, arch)
    prog = arch_mod.to_program(params)
    n = tr["microbatches"]
    rows = batch["tokens"].shape[0] // n
    with jax.default_matmul_precision("highest"):
        grads = [jax.grad(lambda p: loss_fn(
            arch, p, {k: v[i * rows:(i + 1) * rows] for k, v in batch.items()}, NULL_POLICY,
            remat=False, flash_chunk=64, compute_dtype=jnp.float32)[0])(prog) for i in range(n)]
        g = jax.tree.map(lambda *x: sum(x) / n, *grads)
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, tr["clip_norm"] / global_norm(g)), g)
        theirs, _ = opt.update(g, opt.init(prog), prog, jnp.zeros((), jnp.int32))
        step = ref.make_step(cfg, tr, "f32")
        ours, _, _, _ = step(dict(params), {"m": jax.tree.map(jnp.zeros_like, params),
                                            "v": jax.tree.map(jnp.zeros_like, params)},
                             jnp.asarray(0, jnp.int32), batch)
    theirs = arch_mod.from_program(theirs)
    lr = tr["optimizer"]["lr"]
    for k in params:
        # Adam's first step moves each element by about lr; elements whose
        # gradient is near eps may differ by rounding alone
        diff = np.abs(np.asarray(theirs[k]) - np.asarray(ours[k]))
        assert np.mean(diff > 1e-3 * lr) < 1e-3, k
        assert diff.max() < 2 * lr, k
