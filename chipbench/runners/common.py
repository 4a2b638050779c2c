"""What the training runners share: weights from the seed, the program's
optimizer for the configuration, the readings of the program's first steps,
the reference run and the host spans."""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import correctness, flops
from chipbench.traffic import Traffic

def span(name):
    """A host span in the profiler's trace (cheap when no trace is taken)."""
    return jax.profiler.TraceAnnotation(name)


def arch_module(cfg):
    return importlib.import_module(f"chipbench.archs.{cfg['model_type']}")


def reference_module(cfg):
    return importlib.import_module(f"chipbench.reference.{cfg['model_type']}")


def traffic_for(cfg, mix, seed):
    tr = cfg["train"]
    return Traffic(mix, tr["seq_len"], tr["global_batch"], cfg["vocab_size"], seed)


def optimizer(cfg, arch):
    """The program's optimizer for this configuration, checked against the
    recipe the configuration states."""
    from repro.train.optimizer import optimizer_for

    o = cfg["train"]["optimizer"]
    opt = optimizer_for(arch, lr=o["lr"])
    if opt.name != o["name"]:
        raise ValueError(f"program picks {opt.name}, configuration states {o['name']}")
    return opt


def program_weights(cfg, seed):
    """The benchmark's weights for the seed, in the program's layout, made on
    the device in one jitted call."""
    ref, arch = reference_module(cfg), arch_module(cfg)
    make = jax.jit(lambda k: arch.to_program(ref.init_params(cfg, k)))
    return make(ref.key_for_seed(seed))


def first_grad_norms(cfg, m_tree, b1):
    """Per canonical leaf, the norm of the first step's gradient as the
    optimizer got it: Adam's first moment after one step is (1 - b1) g."""
    arch = arch_module(cfg)
    fn = jax.jit(lambda m: {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) / (1 - b1)
                            for k, v in arch.from_program(m).items()})
    return {k: float(v) for k, v in fn(m_tree).items()}


def host_canonical(cfg, params):
    """The program's params on the host, as canonical float32 leaves."""
    arch = arch_module(cfg)
    return {k: np.asarray(v, np.float32)
            for k, v in arch.from_program(jax.device_get(params)).items()}


def reference_as_program(cfg, seed, batches, mode):
    """The reference in the program's place, at precision `mode` (the
    control): its readings in the form `check` takes."""
    ref = reference_module(cfg)
    losses, grad_norms, final = ref.train(cfg, cfg["train"], ref.key_for_seed(seed), batches,
                                          mode=mode)
    return {"losses": losses, "grad_norms": grad_norms, "final": final}


def check(cfg, seed, prog, batches):
    """Run the reference over `batches` from the same weights and compare.

    prog: {'losses', 'grad_norms', 'final'} of the program, with 'final' the
    canonical host params after the last of `batches`.
    -> (correct, rows, readings)."""
    ref = reference_module(cfg)
    key = ref.key_for_seed(seed)
    initial = {k: np.asarray(v) for k, v in
               jax.jit(lambda k: ref.init_params(cfg, k))(key).items()}
    losses, grad_norms, final = ref.train(cfg, cfg["train"], key, batches)
    read = correctness.readings(
        {"losses": prog["losses"], "grad_norms": prog["grad_norms"],
         "change_norms": correctness.change_norms(prog["final"], initial)},
        {"losses": losses, "grad_norms": grad_norms,
         "change_norms": correctness.change_norms(final, initial)})
    ok, rows = correctness.judge(read, cfg["check"]["limits"])
    return ok, rows, read


def step_work(cfg, batch):
    """Per-step counts the metrics need: target tokens, model FLOPs and the
    attention work the packing mask needs."""
    seg = batch["segment_ids"]
    attn_flops, attn_bytes = flops.attention_work(cfg, seg)
    return {"tokens": int(np.sum(batch["labels"] >= 0)), "flops": flops.step_flops(cfg, seg),
            "attn_flops": attn_flops, "attn_bytes": attn_bytes}
