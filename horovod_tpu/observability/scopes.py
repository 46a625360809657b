"""The named scopes of the compiled programs: the one list, and what a scope
path means.

``jax.named_scope`` is metadata only: it changes no instruction, and reaches
every HLO operation as its ``op_name``, a path such as
``jit(step)/shard_map/grad/transpose(jvp(layer_norm))/jit(_var)/mul``. The
profiler carries that path to the trace (stat ``tf_op`` of an ``XLA Ops``
event's metadata), so a step's device time can be read by scope
(docs/observability.md; ``benchmark/readers/xplane_scopes.py``).

A PHASE is a part of the train step (``parallel/data_parallel.py``
``make_train_step``); a SCOPE is a part of the model
(``models/transformer.py``; ``head`` also in ``serving/engine.py``). Both
files open their scopes with the constants below, so a name is spelled
once. ``experts`` is opened inside ``mlp``: :func:`parse` returns the
innermost scope, and a reader that wants the whole feed-forward layer asks
for ``mlp|experts``. ``state_space`` holds a state-space layer's whole mixer
(projections, convolution, recurrence, gated norm) where ``attention`` holds
an attention layer's, and ``linear_attention`` a delta-rule layer's
(projections, convolutions, decay, recurrence, gated norm); ``expert_latent``
the two projections around experts that work in a latent, inside ``mlp``
beside ``experts``. A selective-scan layer's whole mixer is under
``state_space`` too (one kind or the other a model), and ``gated_memory``
holds a gated memory unit's two products and its gate. Inside ``attention`` a
layer that selects key/value blocks opens ``block_index`` (the indexer's
three projections, the pooled rows, the blocks' scores), ``block_select``
(the top-k and the lists the kernel walks) and ``block_attention`` (the
attention over the chosen blocks); its Q/K/V projections, norms, rotation and
cache writes stay ``attention``'s own.

No JAX here: the benchmark's jax-free parent imports this module.
"""

import re

PHASES = GRAD, GRAD_REDUCE, OPTIMIZER = ("grad", "grad_reduce", "optimizer")
SCOPES = (EMBED, LAYER_NORM, RMS_NORM, ATTENTION, MLP, EXPERTS, LOSS,
          HEAD, STATE_SPACE, EXPERT_LATENT, LINEAR_ATTENTION,
          GATED_MEMORY, BLOCK_INDEX, BLOCK_SELECT, BLOCK_ATTENTION) = (
              "embed", "layer_norm", "rms_norm", "attention", "mlp",
              "experts", "loss", "head", "state_space", "expert_latent",
              "linear_attention", "gated_memory", "block_index",
              "block_select", "block_attention")

# What a transform writes around a component of the path it differentiates,
# transposes or batches: ``transpose(jvp(attention))``. Components that are
# no scope (``jit(step)``, ``shard_map``, ``while``, ``body``, ``cond``,
# ``closed_call``, ``checkpoint``, ``rematted_computation``, ``pjit``, an
# einsum's spec, the primitive at the end) are passed over.
_WRAPPED = re.compile(r"^(jvp|transpose|vmap)\((.*)\)$")


def _components(path):
    """Split on ``/`` outside parentheses."""
    depth, start = 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            yield path[start:i]
            start = i + 1
    yield path[start:]


def parse(path):
    """``op_name`` path -> ``(phase, scope, backward)``, or None where the
    path names neither.

    ``phase`` is the first component that is one of :data:`PHASES` (None in
    a program that is no train step); ``scope`` is the INNERMOST component
    that is one of :data:`SCOPES` once the transform wrappers are taken off
    (None for phase-only work such as the optimizer's update);
    ``backward`` says whether a ``transpose(..)`` wraps a component of the
    path: the backward pass, the forward replayed under ``checkpoint``
    included.
    """
    phase = scope = None
    backward = False
    # The trace writes ``<op_name>:<type>``; instructions the compiler merged
    # join their paths with ``;`` and the first one speaks for them.
    path = (path or "").split(";", 1)[0].rsplit(":", 1)[0]
    for comp in _components(path):
        m = _WRAPPED.match(comp)
        while m:
            backward = backward or m.group(1) == "transpose"
            comp = m.group(2)
            m = _WRAPPED.match(comp)
        if comp in PHASES:
            phase = phase or comp
        elif comp in SCOPES:
            scope = comp
    if phase is None and scope is None:
        return None
    return phase, scope, backward
