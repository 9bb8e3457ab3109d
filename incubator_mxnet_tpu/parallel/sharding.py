"""Tensor-parallel sharding rules for Gluon parameters.

The reference's only model-parallel primitive is `group2ctx` manual
placement (SURVEY.md §2.4 TP row).  Here: Megatron-style PartitionSpec
rules matched against a Block's STRUCTURAL parameter paths (e.g.
``encoder.layer0.attention.qkv.weight`` — stable attribute paths from
`Block._collect_params_with_prefix`, not the instance-counter global
names), applied by `shard_params(block, mesh)`.  After placement, any
jitted step over those arrays gets XLA-inserted ICI collectives via
GSPMD propagation — including the Trainer's fused fwd+bwd+update
program, which is how `gluon.Trainer` scales over a mesh with zero
changes to the training loop.

`shard_params` returns a `ShardingReport`: every decision is recorded
and silent full replication is impossible — anything that *looked*
shardable but wasn't (no rule matched, or a mesh axis didn't divide the
dim) is listed, and a warning fires when TP was requested but nothing
was actually sharded.
"""
from __future__ import annotations

import logging
import re
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

log = logging.getLogger(__name__)

__all__ = ["TP_RULES_TRANSFORMER", "TP_RULES_VISION", "ShardingReport",
           "spec_for", "shard_params", "shard_param_tree",
           "data_parallel_spec"]

# (path regex, PartitionSpec) — first match wins; matched with
# re.search against the structural path.  Specs refer to the 'model'
# mesh axis; Dense weights are (out, in), Embedding weights are
# (vocab, units) per gluon/nn/basic_layers.py.
TP_RULES_TRANSFORMER: List[Tuple[str, P]] = [
    # column parallel: QKV projections, fused or split
    (r"(query|key|value|qkv|q_proj|k_proj|v_proj)\.weight$", P("model", None)),
    # column parallel: FFN up / gate (before the bare-proj rule: up_proj/
    # gate_proj must not be captured as row-parallel)
    (r"(ffn_dense1|fc1|dense1|w1|up_proj|gate_proj|inter)\.weight$",
     P("model", None)),
    # row parallel: FFN down
    (r"(ffn_dense2|fc2|dense2|w2|down_proj)\.weight$", P(None, "model")),
    # vocab-sharded: embedding tables (vocab, units) and LM heads (vocab, units)
    (r"(embed|embedding|decoder|lm_head|vocab_proj)[^.]*\.weight$",
     P("model", None)),
    # row parallel: attention output projection — the bare `proj`
    # alternative is anchored to a path segment so it cannot swallow
    # `*_proj` names handled above
    (r"(^|\.)(out_proj|o_proj|proj)\.weight$", P(None, "model")),
    # column parallel: BERT pooler and the MLM transform dense (D, D)
    (r"(pooler|mlm_dense|transform)\.weight$", P("model", None)),
    # EXPLICITLY replicated: tiny classification heads (NSP's (2, D) —
    # out-dim too small for a useful shard) — a rule, not an omission,
    # so the report counts them as justified
    (r"(^|\.)(nsp|cls|classifier)\.weight$", P()),
    # replicated: norms, biases, BN stats
    (r"(gamma|beta|bias|running_mean|running_var)$", P()),
]

# Vision nets (conv zoo): channel parallelism.  Conv weights are OIHW
# (`gluon/nn/conv_layers.py:43`) — shard the OUT-channel dim; `_pad_spec`
# truncates the same rule to P('model', None) for 2-D Dense classifier
# weights (column parallel).  Per-channel 1-D params (BN stats, biases)
# stay replicated — they are tiny, and replication keeps them valid for
# any activation layout XLA picks.  Model-zoo blocks are built from
# HybridSequential, so structural paths are numeric ('features.4.conv0
# .weight'); matching on the `.weight`/statistic SUFFIX is therefore the
# reliable signal, unlike the transformer rules' named-layer patterns.
TP_RULES_VISION: List[Tuple[str, P]] = [
    (r"(gamma|beta|bias|running_mean|running_var)$", P()),
    (r"\.weight$", P("model", None, None, None)),
]


class ShardingReport(dict):
    """``{structural_name: final PartitionSpec}`` plus full accounting.

    - ``sharded``:    name → spec actually placed on ≥1 mesh axis
    - ``replicated``: name → "why" for every fully-replicated param
    - ``fallbacks``:  name → (wanted_spec, reason) where a rule matched
                      but validation had to drop an axis (non-dividing
                      dim / axis missing from the mesh) — the silent-
                      replication trap, now loud
    - ``unmatched``:  names of ndim≥2 params no rule matched
    """

    def __init__(self):
        super().__init__()
        self.sharded: Dict[str, P] = {}
        self.replicated: Dict[str, str] = {}
        self.fallbacks: Dict[str, Tuple[P, str]] = {}
        self.unmatched: List[str] = []
        self.seq_parallel = 0  # attention blocks routed to ring SP
        self.expert_parallel = 0  # MoE blocks routed to all_to_all EP
        self._elems_sharded = 0
        self._elems_justified = 0  # replicated BY RULE/recorded fallback
        self._elems_matrix = 0

    @property
    def coverage(self) -> float:
        """Fraction of matrix (ndim≥2) parameter elements that ended up
        sharded — the honest TP-memory-savings number."""
        return self._elems_sharded / max(1, self._elems_matrix)

    @property
    def accounted(self) -> float:
        """Fraction of matrix-param elements that are either sharded or
        replicated for a STATED reason (an explicit replicate rule, or a
        fallback whose cause is recorded).  100% means no parameter's
        placement is unexplained; anything below points at `unmatched`."""
        return ((self._elems_sharded + self._elems_justified)
                / max(1, self._elems_matrix))

    def summary(self) -> str:
        lines = [f"shard_params: {len(self.sharded)} sharded / "
                 f"{len(self.replicated)} replicated "
                 f"({self.coverage:.0%} of matrix-param elements sharded, "
                 f"{self.accounted:.0%} accounted)"]
        for n, (want, why) in self.fallbacks.items():
            lines.append(f"  FALLBACK {n}: wanted {want} but {why}")
        if self.unmatched:
            lines.append(f"  no rule matched (replicated, UNACCOUNTED): "
                         f"{', '.join(self.unmatched)}")
        return "\n".join(lines)


def spec_for(name: str, shape, rules=None) -> P:
    """Rule lookup only (no mesh validation); P() when nothing matches."""
    spec, _matched = _match_rule(name, rules)
    return _pad_spec(spec, len(shape))


def _match_rule(name: str, rules) -> Tuple[P, bool]:
    for pat, spec in (rules or TP_RULES_TRANSFORMER):
        if re.search(pat, name):
            return spec, True
    return P(), False


def _pad_spec(spec: P, ndim: int) -> P:
    axes = list(spec) + [None] * (ndim - len(spec))
    return P(*axes[:ndim])


def _validate(spec: P, shape, mesh: Mesh) -> Tuple[P, Optional[str]]:
    """Drop axes that can't apply; return (clean spec, reason|None)."""
    axes, reason = [], None
    for dim, ax in zip(shape, list(spec) + [None] * (len(shape) - len(spec))):
        if ax is None:
            axes.append(None)
        elif ax not in mesh.axis_names:
            axes.append(None)
            reason = f"mesh has no '{ax}' axis"
        elif dim % mesh.shape[ax] != 0:
            axes.append(None)
            reason = f"dim {dim} not divisible by {ax}={mesh.shape[ax]}"
        else:
            axes.append(ax)
    return P(*axes), reason


def _structural_params(block) -> Dict[str, object]:
    """Structural-path name → Parameter.  Bare ParameterDict inputs only
    expose instance-counter global names (``dense0_weight``) which the
    default path-anchored TP rules can never match — warn loudly so a
    `shard_params(net.collect_params(), mesh)` call doesn't silently
    train fully replicated; pass the Block itself instead."""
    if hasattr(block, "_collect_params_with_prefix"):
        return dict(block._collect_params_with_prefix())
    warnings.warn(
        "shard_params: got a ParameterDict — TP rules match structural "
        "paths ('encoder.layer0.attention.qkv.weight') which only a Block "
        "provides; with global names the default rules will not shard "
        "anything. Pass the Block itself (shard_params(net, mesh)).",
        stacklevel=3)
    return dict(block.collect_params().items()
                if hasattr(block, "collect_params") else block.items())


def shard_params(block, mesh: Mesh, rules=None, dp_axis: Optional[str] = None,
                 warn: bool = True, min_fsdp_elems: int = 2 ** 16
                 ) -> ShardingReport:
    """Assign NamedShardings to every initialized Parameter of `block`
    and device_put data (and grad buffers) accordingly.

    ``dp_axis``: optional FSDP-style fallback — params the TP rules left
    fully replicated and larger than `min_fsdp_elems` are sharded on
    their first dividing dim over this axis (XLA all-gathers on use;
    ZeRO-3 memory profile).  Returns a `ShardingReport`.
    """
    report = ShardingReport()
    tp_requested = any(
        ax in mesh.axis_names and mesh.shape[ax] > 1
        for _pat, spec in (rules or TP_RULES_TRANSFORMER)
        for ax in spec if ax is not None)
    for name, p in _structural_params(block).items():
        if p._data_nd is None:
            continue
        want, matched = _match_rule(name, rules)
        spec, reason = _validate(want, p.shape, mesh)
        # the TP intent failed → record the fallback BEFORE any FSDP
        # rescue, so the report never hides a broken TP rule
        tp_failed = matched and any(ax is not None for ax in want) \
            and not any(ax is not None for ax in spec)
        if tp_failed:
            report.fallbacks[name] = (want, reason or "validation dropped axes")
        if dp_axis and len(p.shape) >= 1 and not any(spec) \
                and _nelems(p.shape) >= min_fsdp_elems:
            spec = _fsdp_spec(p.shape, mesh, dp_axis)
        _place(p, mesh, spec)
        report[name] = spec
        if any(ax is not None for ax in spec):
            report.sharded[name] = spec
            report._elems_sharded += _nelems(p.shape) if len(p.shape) >= 2 else 0
        else:
            if tp_failed:
                report.replicated[name] = reason or "validation"
                report._elems_justified += \
                    _nelems(p.shape) if len(p.shape) >= 2 else 0
            elif not matched and len(p.shape) >= 2:
                report.unmatched.append(name)
                report.replicated[name] = "no rule matched"
            else:
                report.replicated[name] = "rule: replicated"
                report._elems_justified += \
                    _nelems(p.shape) if len(p.shape) >= 2 else 0
        if len(p.shape) >= 2:
            report._elems_matrix += _nelems(p.shape)
    if warn:
        if report.fallbacks:
            warnings.warn("shard_params: some matched TP rules fell back to "
                          "replication —\n" + report.summary(), stacklevel=2)
        elif tp_requested and not report.sharded:
            warnings.warn("shard_params: TP axes requested but NO parameter "
                          "was sharded (model would train fully replicated) —\n"
                          + report.summary(), stacklevel=2)
    # sequence parallelism: a >1 `seq` axis routes every attention block
    # with a set_seq_parallel hook through ring attention (SURVEY.md
    # §5.7 — the Gluon doorway to SP)
    if "seq" in mesh.axis_names and mesh.shape["seq"] > 1:
        report.seq_parallel = _enable_hook(block, "set_seq_parallel", mesh)
        log.info("shard_params: seq=%d — ring attention enabled on %d "
                 "attention block(s)", mesh.shape["seq"],
                 report.seq_parallel)
    # expert parallelism: a >1 `expert` axis shards MoE expert weights
    # and routes tokens via all_to_all (gluon.contrib.MoEFFN)
    if "expert" in mesh.axis_names and mesh.shape["expert"] > 1:
        report.expert_parallel = _enable_hook(
            block, "set_expert_parallel", mesh)
        log.info("shard_params: expert=%d — all_to_all dispatch enabled "
                 "on %d MoE block(s)", mesh.shape["expert"],
                 report.expert_parallel)
    if hasattr(block, "_invalidate_cached_program"):
        # the forward is re-traced with the mesh in context, so that its
        # Pallas kernels are emitted per shard (ops/mosaic.py)
        block._mesh = mesh
        block._invalidate_cached_program()
    log.info(report.summary())
    return report


def _enable_hook(block, method: str, mesh: Mesh) -> int:
    """Call ``method(mesh)`` on every block in the tree that exposes it
    (e.g. MultiHeadAttention.set_seq_parallel,
    MoEFFN.set_expert_parallel) via Block.apply.  Returns the count of
    DISTINCT blocks flipped — Block.apply visits a shared sub-Block once
    per parent, so dedup by identity or weight-shared attention would
    double-count."""
    seen = set()

    def visit(b):
        if hasattr(b, method) and id(b) not in seen:
            seen.add(id(b))
            getattr(b, method)(mesh)

    block.apply(visit)
    return len(seen)


def _nelems(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def _fsdp_spec(shape, mesh: Mesh, dp_axis: str) -> P:
    axes = [None] * len(shape)
    if dp_axis not in mesh.axis_names or mesh.shape[dp_axis] <= 1:
        return P(*axes)  # size-1 axis would be fake sharding
    n = mesh.shape[dp_axis]
    for i, d in enumerate(shape):
        if d % n == 0:
            axes[i] = dp_axis
            break
    return P(*axes)


def _place(p, mesh: Mesh, spec: P) -> None:
    p.sharding = spec
    sh = NamedSharding(mesh, spec)
    p._data_nd._data = jax.device_put(p._data_nd._data, sh)
    g = p._data_nd._grad
    if g is not None and g._lazy is None:
        g._data = jax.device_put(g._data, sh)


def shard_param_tree(params, mesh: Mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, spec_tree)


def data_parallel_spec(batch_shape, mesh: Mesh, axis: str = "data") -> P:
    return P(axis, *([None] * (len(batch_shape) - 1)))
