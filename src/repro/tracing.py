"""The names the program gives its work in a profiler trace, in one place.

Device scopes (`scope`, a `jax.named_scope`) name the layers of the compiled
train step: the SPMD `train_step` and `PipelineEngine`'s stage functions run
the same model and optimizer code, so both carry them. A TPU trace names each
operation by its HLO instruction; the scope is in the compiled program's
`op_name` metadata, and `scope_of` reads it from there. Scopes nest
(`attn_core` inside `attn_proj`); an operation belongs to the innermost.

Host spans (`span`, a `jax.profiler.TraceAnnotation`) name what the host
loops do around the device, with counts as the event's stats; `step` marks
one training step. They land in the same trace as the device operations, on
one clock. When no profile is being captured a span costs a check in C++
and the passing of its arguments, and `nbytes` counts nothing.

`profile` captures the steps after the first few into a directory, for
`python -m repro.launch.train --profile-dir DIR`; open it with XProf or
TensorBoard's profile plugin.
"""
from __future__ import annotations

import contextlib
import re

import jax

# device scopes, by the layer each names
SCOPES = (
    "attn_core",  # attention's core: dense, chunked or Pallas (models/attention.py)
    "attn_proj",  # the rest of attention: q/k/v/o, qk-norm, RoPE, the jnp paths' GQA repeat
    "mlp",  # the dense gated FFN (models/mlp.py)
    "lm_head",  # final norm, logits, log-softmax, z-loss and nll (models/model.py, engine/pipeline.py)
    "optimizer",  # gradient scale, global norm, clip and update (train/)
)

# host spans, by the loop that opens them
SPANS = (
    # launch/train.run_spmd, inside each step
    "batch",  # bytes: the batch sent to the device
    "dispatch",  # the jitted step's call
    "wait_state",  # block on the new state
    "detect",  # pack_stats, the predictor and Detector.observe_iteration; attn_tiles: the
    # (q, k) tiles of one causal attention call over the batch at the kernel's block
    # sizes, attn_tiles_run: those its tile table lets run (host numpy, no device work)
    "checkpoint",  # bytes: the state saved, when it saves
    # engine/pipeline.PipelineEngine, inside each iteration
    "stage_params",  # bytes: parameters placed on the stage meshes
    "F", "B",  # one chunk; replica, stage, mb, and built=1 on a stage function's first call
    "send",  # bytes: an activation or gradient moved to the next stage's mesh
    "loss_sync",  # the host's float() of the microbatch losses
    "apply_grads",  # bytes: gradients brought to the host; the update
    "apply_plan",  # rebuilding meshes and policies for a new plan
)

STEP = "train"  # the step annotation's name; its stat step_num is the step

_WRAPPED = re.compile(r"^[\w.]+\((.*)\)$")


def scope(name):
    """The device scope `name` (one of SCOPES) for the operations traced in it."""
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; known: {SCOPES}")
    return jax.named_scope(name)


def span(name, **counts):
    """The host span `name` (one of SPANS), with `counts` as its stats."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}; known: {SPANS}")
    return jax.profiler.TraceAnnotation(name, **counts)


def step(num):
    """The annotation of training step `num`."""
    return jax.profiler.StepTraceAnnotation(STEP, step_num=num)


def scope_of(op_name):
    """The innermost program scope in an operation's name stack, or None.

    A scope counts only as a whole component of the stack, also when JAX
    wraps it as `jvp(mlp)` or `transpose(jvp(mlp))`; a parameter path such as
    `state['params']['layers'][0]['ffn']` names no scope."""
    for part in reversed(op_name.split("/")):
        while (m := _WRAPPED.match(part)):
            part = m.group(1)
        if part in SCOPES:
            return part
    return None


@contextlib.contextmanager
def profile(directory, skip=2):
    """Capture a profile into `directory` from the step after the first
    `skip`. Yields `at(i)`, to call at the top of step i (counted from 0);
    with no directory it does nothing."""
    started = False

    def at(i):
        nonlocal started
        if directory and not started and i >= skip:
            jax.profiler.start_trace(directory)
            started = True

    try:
        yield at
    finally:
        if started:
            jax.profiler.stop_trace()


def nbytes(tree):
    """Bytes of the arrays in a pytree, for a span's `bytes` stat; 0 when no
    profile is being captured, since then nothing records it."""
    if not jax.profiler.TraceAnnotation.is_enabled():
        return 0
    return sum(x.nbytes for x in jax.tree.leaves(tree))
