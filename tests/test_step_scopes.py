"""The named scopes of the compiled programs are an account that closes:
``observability/scopes.py`` owns what a scope path means, and every
operation of the train step that ``make_train_step`` builds lies in one
phase (``benchmark/readers/xplane_scopes.py`` reads device time by them)."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from horovod_tpu import parallel
from horovod_tpu.models import transformer as tfm
from horovod_tpu.observability import scopes

# Paths as the compiler and the profiler write them (copied from compiled
# steps of this repo, CPU and v5e), and what each means.
PATHS = {
    "forward": (
        "jit(step)/shard_map/grad/jvp(attention)/bshk,hkd->bsd/dot_general",
        ("grad", "attention", False)),
    "backward": (
        "jit(step)/grad/transpose(jvp(mlp))/bsf,fd->bsd/dot_general",
        ("grad", "mlp", True)),
    "trace_stat_with_type": (
        "jit(step)/grad/jvp(layer_norm)/convert_element_type:",
        ("grad", "layer_norm", False)),
    "nested_jit": (
        "jit(step)/shard_map/grad/transpose(jvp(layer_norm))/jit(_var)/"
        "jit(_where)/select_n",
        ("grad", "layer_norm", True)),
    "under_checkpoint": (
        "jit(step)/shard_map/grad/transpose(jvp(grad))/jvp()/checkpoint/"
        "rematted_computation/attention/bshk,hkd->bsd/dot_general",
        ("grad", "attention", True)),
    "chunked_loss_while": (
        "jit(step)/shard_map/grad/jvp(loss)/while/body/closed_call/"
        "bsd,vd->bsv/dot_general",
        ("grad", "loss", False)),
    "chunked_loss_replayed": (
        "jit(step)/shard_map/grad/transpose(jvp(loss))/while/body/"
        "closed_call/checkpoint/rematted_computation/jit(log_softmax)/exp",
        ("grad", "loss", True)),
    "embed_scatter": (
        "jit(step)/shard_map/grad/transpose(jvp(embed))/scatter-add",
        ("grad", "embed", True)),
    "phase_only_grad": (
        "jit(step)/shard_map/grad/jvp()/reduce_sum",
        ("grad", None, False)),
    "grad_reduce": (
        "jit(step)/shard_map/grad_reduce/psum",
        ("grad_reduce", None, False)),
    "optimizer": (
        "jit(step)/shard_map/optimizer/jit(_where)/select_n",
        ("optimizer", None, False)),
    "experts_inside_mlp": (
        "jit(chunk)/mlp/experts/dot_general",
        (None, "experts", False)),
    "rms_norm": (
        "jit(decode)/rms_norm/rsqrt", (None, "rms_norm", False)),
    "head": (
        "jit(decode)/head/bsd,vd->bsv/dot_general", (None, "head", False)),
    "merged_instructions": (
        "jit(step)/grad/transpose(jvp(attention))/reshape;"
        "jit(step)/optimizer/transpose:",
        ("grad", "attention", True)),
    "unknown": ("jit(init_params)/jit(_normal)/mul", None),
    "argument_copy": ("params['layers'][21]['w_out']:", None),
    "no_path": (None, None),
}


@pytest.mark.parametrize("case", sorted(PATHS))
def test_parse(case):
    path, want = PATHS[case]
    assert scopes.parse(path) == want


def test_names_are_spelled_once():
    assert scopes.PHASES == ("grad", "grad_reduce", "optimizer")
    assert scopes.SCOPES == ("embed", "layer_norm", "rms_norm", "attention",
                             "mlp", "experts", "loss", "head", "state_space",
                             "expert_latent", "linear_attention",
                             "gated_memory", "block_index", "block_select",
                             "block_attention")
    assert scopes.parse("jit(decode)/gated_memory/dot_general") == (
        None, "gated_memory", False)
    # a selecting layer's scopes lie INSIDE attention: the innermost is read
    assert scopes.parse(
        "jit(chunk)/attention/block_index/bsd,dgjk->bsgjk/dot_general") == (
            None, "block_index", False)
    assert not set(scopes.PHASES) & set(scopes.SCOPES)


# ---- the compiled step ----------------------------------------------------

NO_WORK = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")
# The CPU compiler names what it makes itself after the HLO instruction it
# came from (``jit(step)/shard_map/broadcast.103``) or after the shard_map
# alone. No scope was open there.
COMPILER_MADE = re.compile(r"jit\(step\)/shard_map(/[a-z\-]+\.\d+)?")
# Share of grad's instructions in no scope: the batch's slices, the sums of
# the mean, and under remat the converts the CPU gives the replay (2.7 %).
UNSCOPED_GRAD_LIMIT = 0.05
# With accumulation, also the scan's add and the 1/accum_steps scaling of
# every leaf (12.6 % of this tiny model's instructions; no benchmark cell
# accumulates, so none of the measured device time).
UNSCOPED_GRAD_LIMIT_ACCUM = 0.15


def _opcode(line):
    rest = line.split(" = ", 1)[1]
    if rest.startswith("("):                       # a tuple type
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:].lstrip()
    else:
        rest = rest.split(" ", 1)[1]
    return rest.split("(", 1)[0]


def _instructions(text):
    """-> [(opcode, op_name or None)] of every instruction of every
    computation of a compiled module's text."""
    out = []
    for line in text.splitlines():
        line = line.strip()
        if " = " not in line or line.endswith("{") \
                or line.startswith("HloModule"):
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        out.append((_opcode(line), m.group(1) if m else None))
    return out


def _compiled_step(loss_chunk, remat, accum_steps=1):
    cfg = dataclasses.replace(tfm.tiny(), loss_chunk=loss_chunk, remat=remat)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    tx = optax.adamw(1e-4)
    params = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    step = parallel.make_train_step(
        lambda p, b: tfm.loss_fn(p, b, cfg), tx, mesh,
        accum_steps=accum_steps)
    batch = {"tokens": jax.ShapeDtypeStruct((4, 33), jnp.int32)}
    return step.lower(params, jax.eval_shape(tx.init, params),
                      batch).compile().as_text()


@pytest.mark.parametrize("loss_chunk,remat,accum_steps", [
    (0, False, 1), (16, False, 1), (0, True, 1), (16, True, 1),
    (16, False, 2)])
def test_every_instruction_of_the_step_lies_in_a_phase(monkeypatch,
                                                       loss_chunk, remat,
                                                       accum_steps):
    if not loss_chunk:
        # the program picks: 4 trips of a shard's [2, 8] at this tiny size
        monkeypatch.setattr(tfm, "_LOSS_ROWS", 16)
    parsed = []
    for opcode, path in _instructions(
            _compiled_step(loss_chunk, remat, accum_steps)):
        # what the program traced carries the whole path from jit(step) on
        if opcode in NO_WORK or not (path or "").startswith("jit(step)/") \
                or COMPILER_MADE.fullmatch(path):
            continue
        found = scopes.parse(path)
        assert found and found[0], f"{opcode} in no phase: {path}"
        parsed.append((opcode, path, found))
    assert len(parsed) > 500                         # not vacuously
    by_phase = {p: [x for x in parsed if x[2][0] == p] for p in scopes.PHASES}
    assert all(by_phase.values())
    # AdamW's update: the one place of the step that takes a square root
    roots = [x for x in parsed if x[0] == "sqrt"]
    assert roots and all(x[2][0] == "optimizer" for x in roots)
    # every collective is the gradients' (the loss's mean included)
    reduces = [x for x in parsed if x[0].startswith("all-reduce")]
    assert reduces and all(x[2][0] == "grad_reduce" for x in reduces)
    # one loop, the forward's: it takes each chunk's gradients too
    loops = [x for x in parsed if "while" in x[1] and x[2][1] == "loss"]
    assert loops and not any(x[2][2] for x in loops)
    unscoped = [x for x in by_phase["grad"] if x[2][1] is None]
    limit = UNSCOPED_GRAD_LIMIT if accum_steps == 1 \
        else UNSCOPED_GRAD_LIMIT_ACCUM
    assert len(unscoped) <= limit * len(by_phase["grad"]), \
        sorted({x[1] for x in unscoped})
    assert not any(x[2][1] == "head" for x in parsed)   # the loss projects


def test_serving_forward_projects_under_head():
    cfg = tfm.tiny()
    params = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    text = jax.jit(lambda p, t: tfm.forward(p, t, cfg)).lower(
        params, jax.ShapeDtypeStruct((2, 16), jnp.int32)).compile().as_text()
    found = {path: scopes.parse(path) for _, path in _instructions(text)
             if path and "bsd,vd->bsv" in path}
    assert found and all(v == (None, "head", False) for v in found.values())
    # and nothing of the model is outside the scopes
    loose = {path for opcode, path in _instructions(text)
             if opcode not in NO_WORK and (path or "").startswith("jit(")
             and "/" in path and scopes.parse(path) is None}
    assert not loose, sorted(loose)
