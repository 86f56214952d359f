"""Logical-axis → mesh-axis sharding rules (MaxText-style), per phase.

Parameters carry *logical* axis names (see models/layers.py ParamDef); a rule
set maps those to physical mesh axes. Single-pod mesh: ("data", "model");
multi-pod adds a leading "pod" axis that joins the FSDP/batch dimension.

Baseline layout (paper-faithful starting point; §Perf iterates from here):
  - weights:    TP over "model" (heads / mlp / vocab / rnn / inner),
                FSDP over ("pod","data") on the embed dim
  - batch:      over ("pod","data")
  - KV cache:   sequence-sharded over "model" (decode context parallelism —
                the softmax/psum combine is handled by SPMD partitioning)

Serving layout (phase="serve"): the serving stack's acceptance bar is
BIT-IDENTICAL greedy outputs vs single device, which rules out any layout
that splits a contraction dimension across devices (partial matmuls +
psum/AllReduce re-associate float sums). The serve rules therefore shard
only *batch-like* dims (slot/page/snapshot-row batch → "data"; q heads, KV
heads, expert index → "model" — attention heads and experts are batch dims
of their einsums) and *output* dims of matmuls whose contraction side stays
replicated (vocab, mlp-up, rnn-up). Down-projections keep their contraction
axis replicated via the ``*_in`` weight axes, and the ``*_act`` activation
keys force an all-gather right before each down-projection so the
contraction itself runs identically on every device. All-gathers move bits
but never re-associate sums, so the whole forward pass stays bit-exact.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def rules_for(mesh: Mesh, phase: str, *, shard_batch: bool = True,
              weight_stationary: bool = False,
              expert_parallel: bool = False) -> dict:
    """Baseline layout, or the §Perf `weight_stationary` decode layout.

    weight_stationary (decode only): activations are tiny at one-token-per-
    sequence, so REPLICATE them over the batch axes and fully 2D-shard every
    weight — matmuls contract against sharded weights and psum small
    activations instead of all-gathering multi-GB weights each layer (the
    baseline's dominant decode collective). KV caches stay (batch→data,
    seq→model)-sharded.
    """
    axes = mesh.axis_names
    multi_pod = "pod" in axes
    fsdp = ("pod", "data") if multi_pod else ("data",)
    batch = fsdp if shard_batch else ()
    if phase == "serve":
        return _serve_rules(mesh, batch)
    rules = {
        "phase": phase,
        "mesh": mesh,
        "batch": batch,
        "cache_batch": batch,
        "fsdp": fsdp,
        "vocab": ("model",),
        "embed": fsdp,
        "heads": ("model",),
        "heads_in": ("model",),   # wo contraction side (serve: replicated)
        "heads_act": ("model",),  # attention output pre-wo (serve: gathered)
        "kv_heads": (),
        "head_dim": (),
        "mlp": ("model",),
        "mlp_in": ("model",),     # dense-MLP wo contraction side
        "mlp_act": ("model",),    # MLP hidden pre-wo
        "experts": (),
        "moe_mlp": ("model",),    # MoE wi/wo hidden dim
        "moe_embed": fsdp,
        "moe_tokens": batch,      # xe group dim (default: follow the batch)
        "experts_run": (),        # xe expert dim (EP mode: the fsdp axis)
        "rnn": ("model",),
        "rnn_in": ("model",),     # RG-LRU wo contraction side
        "rnn_act": ("model",),    # RG-LRU mixed output pre-wo
        # xLSTM inner dims: replicated over `model` (§Perf iteration 2) —
        # TP of a 2048-wide recurrence over 16 shards made every mLSTM chunk
        # all-gather its state/qkv (45GB/step); a 350M-class recurrent model
        # wants pure data parallelism on this mesh.
        "inner": (),
        "inner_out": (),
        "slstm_inner": (),
        "conv": (),
        "norm": (),
        "layers": (),
        "kv_seq": ("model",),
        None: (),
    }
    if weight_stationary:
        assert phase == "decode", "weight-stationary layout is a decode mode"
        # Activations replicate; weights keep their 2D sharding and are
        # contracted IN PLACE (psum of small partials). Caches keep the
        # sharded batch via "cache_batch".
        rules["batch"] = ()
        rules["moe_tokens"] = ()
    if expert_parallel:
        # experts live on the fsdp axis; tokens all-to-all to their expert
        rules["experts"] = fsdp
        rules["moe_embed"] = ()
        rules["experts_run"] = fsdp
        rules["moe_tokens"] = ()
    return rules


def _serve_rules(mesh: Mesh, batch) -> dict:
    """The bit-exact serving layout (see module docstring).

    batch/page/row axes → "data"; per-head and per-expert batch dims plus
    replicated-contraction output dims (vocab / mlp-up / rnn-up) → "model";
    every contraction side (embed, ``*_in``) and every pre-down-projection
    activation (``*_act``) replicated, so no float sum is ever split.
    """
    return {
        "phase": "serve",
        "mesh": mesh,
        "batch": batch,
        "cache_batch": batch,
        "fsdp": (),
        "vocab": ("model",),       # unembed output dim; embed-table rows
        "embed": (),               # every input contraction: replicated
        "heads": ("model",),       # q heads: a batch dim of attention
        "heads_in": (),            # wo contracts over heads → replicated
        "heads_act": (),           # gather attention output before wo
        "kv_heads": ("model",),    # KV cache / page-pool head dim
        "head_dim": (),
        "mlp": ("model",),         # wi/wg output dim (contraction replicated)
        "mlp_in": (),              # wo contracts over F → replicated
        "mlp_act": (),             # gather hidden before wo
        "experts": ("model",),     # expert parallelism: E is a batch dim
        "moe_mlp": (),             # per-expert F: contracted by MoE wo
        "moe_embed": (),
        "moe_tokens": (),
        "experts_run": ("model",),  # dispatched tokens follow their expert
        "rnn": ("model",),         # RG-LRU channels: elementwise recurrence
        "rnn_in": (),              # wo contracts over R → replicated
        "rnn_act": (),             # gather mixed output before wo
        # xLSTM / sLSTM inner dims stay replicated (see baseline comment —
        # and their qkv projections contract over "inner", which a sharded
        # inner dim would split)
        "inner": (),
        "inner_out": (),
        "slstm_inner": (),
        "conv": (),
        "norm": (),
        "layers": (),
        "kv_seq": (),              # no sequence parallelism: the softmax
                                   # combine re-associates sums (not bit-safe)
        None: (),
    }


def _axes_to_spec(axes: Sequence[Optional[str]], rules: dict) -> P:
    out = []
    for a in axes:
        phys = rules.get(a, ())
        if isinstance(phys, str):
            phys = (phys,)
        if len(phys) == 0:
            out.append(None)
        elif len(phys) == 1:
            out.append(phys[0])
        else:
            out.append(tuple(phys))
    return P(*out)


def param_pspecs(logical_tree, rules: dict):
    """Tree of logical-axis tuples -> tree of PartitionSpec."""
    return jax.tree.map(
        lambda axes: _axes_to_spec(axes, rules),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x),
    )


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        spec_tree, is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# Cache shardings
# ---------------------------------------------------------------------------


def cache_pspecs(cfg, rules: dict):
    """PartitionSpecs mirroring ``transformer.cache_spec`` structurally.

    Every physical axis comes from the rule set: train/decode phases
    sequence-shard attention KV over "model" and channel-shard recurrent
    state; the serve phase instead shards the batch axis (slot / page /
    snapshot row) over "data" and the KV-head / recurrent-channel dims over
    "model" (both are batch-like — bit-safe). The same specs cover the dense
    per-slot cache, the paged page pool (batch = pages) and the snapshot
    arena (batch = rows), which all reuse the cache pytree structure.
    """
    from repro.configs import base as cfgbase

    batch = rules.get("cache_batch", rules["batch"])
    b = batch if len(batch) > 1 else (batch[0] if batch else None)
    kv = rules["kv_seq"][0] if rules["kv_seq"] else None
    kvh = rules.get("kv_heads", ())
    kvh = kvh[0] if kvh else None
    rnn = rules.get("rnn", ())
    ch = rnn[0] if rnn else None

    def block_specs(kind, lead):
        if kind in (cfgbase.ATTN, cfgbase.ATTN_MOE, cfgbase.LOCAL_ATTN):
            s = P(*lead, b, kv, kvh, None)
            return {"k": s, "v": s}
        if kind == cfgbase.RECURRENT:
            return {"h": P(*lead, b, ch), "conv": P(*lead, b, None, ch)}
        if kind == cfgbase.MLSTM:
            return {"state": (P(*lead, b, None, None, None),  # C [B,H,mhd,mhd]
                              P(*lead, b, None, None),         # n [B,H,mhd]
                              P(*lead, b, None)),               # m [B,H]
                    "conv": P(*lead, b, None, None)}
        if kind == cfgbase.SLSTM:
            s = P(*lead, b, None)        # replicated channels (see rules)
            return {"state": (s, s, s, s)}
        raise ValueError(kind)

    out = {"scan": {}}
    for i, kind in enumerate(cfg.pattern):
        out["scan"][f"sub{i}"] = block_specs(kind, (None,))
    for j, kind in enumerate(cfg.tail_kinds):
        out[f"tail{j}"] = block_specs(kind, ())
    return out


# ---------------------------------------------------------------------------
# Batch / IO shardings
# ---------------------------------------------------------------------------


def batch_pspecs(cfg, rules: dict, phase: str):
    batch = rules["batch"]
    b = batch if len(batch) != 1 else (batch[0] if batch else None)
    if not batch:
        b = None
    specs = {"positions": P(b, None)}
    if cfg.modality == "audio_frames":
        specs["frames"] = P(b, None, None)
    else:
        specs["tokens"] = P(b, None)
    if phase == "train":
        specs["labels"] = P(b, None)
    return specs


# ---------------------------------------------------------------------------
# Activation-constraint context (used inside model code via current_rules())
# ---------------------------------------------------------------------------

_tls = threading.local()


def current_rules() -> Optional[dict]:
    return getattr(_tls, "rules", None)


@contextlib.contextmanager
def use_rules(rules: dict):
    prev = getattr(_tls, "rules", None)
    _tls.rules = rules
    try:
        yield
    finally:
        _tls.rules = prev


def constrain(x, *axes):
    """with_sharding_constraint by logical axes, no-op outside a rules ctx.

    Rule sets carry their mesh, so the constraint is a full ``NamedSharding``
    — usable from any call site (the serving jits run under ``use_rules``
    with no ambient ``with mesh:`` context manager). Constraints whose spec
    does not divide the dim are ignored by the partitioner (replicated),
    which keeps small test configs (e.g. 2 KV heads on a 4-way "model"
    axis) correct — just unsharded on that dim.
    """
    rules = current_rules()
    if rules is None:
        return x
    spec = _axes_to_spec(axes, rules)
    mesh = rules.get("mesh")
    if mesh is not None:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)


def per_shard(fn, args, in_axes, out_axes):
    """Call ``fn(*args)`` once per device on its own shard when the active
    rule set spans several devices, and plainly otherwise.

    XLA cannot partition a Pallas TPU kernel, so the model routes every
    kernel call through here. ``in_axes`` gives each argument's logical
    axes (one tuple per argument, ``()`` for a scalar); ``out_axes`` gives
    the result's, or a tuple of such tuples for several results. A logical
    axis whose mesh extent does not divide some argument's dim is
    replicated in every argument and result, so the shards stay
    consistent (q heads and KV heads name the same axis for that reason).
    Each shard runs the kernel on whole batch rows and heads, so the
    values are those of the single-device call.
    """
    rules = current_rules()
    mesh = rules.get("mesh") if rules is not None else None
    if mesh is None or mesh.devices.size == 1:
        return fn(*args)

    def extent(name):
        phys = rules.get(name, ())
        phys = (phys,) if isinstance(phys, str) else phys
        n = 1
        for m in phys:
            n *= mesh.shape[m]
        return n

    dropped = {a for x, axes in zip(args, in_axes)
               for dim, a in zip(jnp.shape(x), axes)
               if a is not None and dim % extent(a)}

    def spec(axes):
        return _axes_to_spec([None if a in dropped else a for a in axes],
                             rules)

    many = bool(out_axes) and isinstance(out_axes[0], tuple)
    out_specs = tuple(spec(a) for a in out_axes) if many else spec(out_axes)
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=tuple(spec(a) for a in in_axes),
                         out_specs=out_specs, check_vma=False)(*args)


# ---------------------------------------------------------------------------
# Committed placement (serving): device_put with divisibility fallback
# ---------------------------------------------------------------------------


def _divisible_spec(shape, spec: P, mesh: Mesh) -> P:
    """Drop spec entries whose mesh-axis extent does not divide the dim.

    ``jax.device_put`` (unlike in-jit constraints) refuses uneven shardings;
    replicating the offending dim preserves values exactly, so a config
    whose KV heads / slots / pages don't divide the mesh still serves
    correctly — that dim just stays unsharded.
    """
    out = []
    for i, dim in enumerate(shape):
        entry = spec[i] if i < len(spec) else None
        if entry is not None:
            names = (entry,) if isinstance(entry, str) else tuple(entry)
            extent = 1
            for n in names:
                extent *= mesh.shape[n]
            if extent == 0 or dim % extent != 0:
                entry = None
        out.append(entry)
    return P(*out)


def put_shardings(tree, spec_tree, mesh: Mesh):
    """Per-leaf ``NamedSharding``s for a pytree of arrays or shapes, from
    per-leaf ``PartitionSpec``s (same structure), replicating dims that do
    not divide their mesh axes."""
    return jax.tree.map(
        lambda x, spec: NamedSharding(mesh,
                                      _divisible_spec(x.shape, spec, mesh)),
        tree, spec_tree)


def shard_put(tree, spec_tree, mesh: Mesh):
    """``device_put`` a pytree with per-leaf ``PartitionSpec``s (same
    structure), falling back to replication on non-divisible dims."""
    return jax.device_put(tree, put_shardings(tree, spec_tree, mesh))
