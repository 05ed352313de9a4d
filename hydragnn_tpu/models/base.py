"""`BaseStack` — the shared encoder/multihead-decoder pattern of the zoo.

Re-designs the reference's `Base` abstract stack
(reference: hydragnn/models/Base.py:27-347) as a flax module:

* encoder = `num_conv_layers` message-passing convs (subclass hook
  `make_conv`), each followed by masked BatchNorm + activation
  (reference: Base.py:122-128, 303-318),
* decoder = one MLP shared across graph heads (`graph_shared`,
  reference: Base.py:223-231) + per-head MLPs; node heads in `mlp`,
  `mlp_per_node` or `conv` variants (reference: Base.py:262-290),
* GaussianNLL variance widening `head_dim * (1 + var_output)`
  (reference: Base.py:74-77, 255).

Everything is static-shape over a padded `GraphBatch`; padding is masked in
the BatchNorm statistics and the pooling, so outputs at padding slots are
garbage-but-finite and ignored by the loss.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..config.config import HeadConfig, ModelConfig
from ..graphs.batch import GraphBatch
from ..ops.activations import activation_function_selection
from ..ops.segment import global_mean_pool
from .layers import MLP, MLPNode, MaskedBatchNorm, node_index_in_graph


def _remat_call(conv: nn.Module, *args):
    """Activation-checkpoint a conv layer's application: recompute its
    forward during the backward pass instead of storing intermediates
    (reference: conv checkpointing, Base.py:299-301,310-315 / create.py:424
    — there via torch.utils.checkpoint; here flax `nn.remat` on the call).
    Param paths are untouched, so checkpointing is a pure memory/FLOPs
    trade."""
    return nn.remat(lambda mdl, *a: mdl(*a))(conv, *args)


class VecHeadConv(nn.Module):
    """Adapter presenting a vector-channel conv (PainnConv/PNAEqConv,
    signature ``conv(s, v, batch, cargs) -> (s, v)``) as a Base-decode
    conv-head layer (``(h, pos, batch, cargs) -> (h, pos)``).

    The stack's encoder stashes its final vector channel in
    ``cargs["vec_channel_encoder"]``; decode resets the working key
    ``cargs["vec_channel"]`` to it at the start of every conv head, and the
    adapter threads it through that head's conv layers (reference:
    PAINNStack.py:139-145 — node conv heads reuse the encoder's ``v``;
    unlike the reference we do not leak one head's final state into the
    next head). Re-zeroes when feature dims mismatch (e.g. a 1-layer
    encoder whose last conv skipped the vector re-embedding)."""
    conv: nn.Module

    @nn.compact
    def __call__(self, h, pos, batch, cargs):
        v = cargs.get("vec_channel")
        if v is None or v.shape[-1] != h.shape[-1]:
            v = jnp.zeros((h.shape[0], 3, h.shape[-1]), h.dtype)
        s, v = self.conv(h, v, batch, cargs)
        cargs["vec_channel"] = v
        return s, pos


class BaseStack(nn.Module):
    """Abstract conv stack + multihead decoder. Subclasses override
    `make_conv` (and optionally `conv_args` / `initial_node_features` /
    `use_batch_norm`)."""

    cfg: ModelConfig
    use_batch_norm: bool = True

    # ------------------------------------------------------------- hooks --
    def make_conv(self, in_dim: int, out_dim: int, idx: int,
                  final: bool = False) -> nn.Module:
        """`final` marks the last conv of a (sub)stack — GAT averages heads
        there instead of concatenating (reference: GATStack.py:35-47)."""
        raise NotImplementedError

    def conv_args(self, batch: GraphBatch) -> Dict[str, Any]:
        """Stack-specific precomputation (edge vectors, rbf, triplets...) —
        reference: Base._conv_args overridden per stack (Base.py:130)."""
        return {}

    def initial_node_features(self, batch: GraphBatch, cargs) -> jnp.ndarray:
        return batch.x

    # ------------------------------------------------------------ forward --
    @nn.compact
    def __call__(self, batch: GraphBatch, train: bool = False):
        cfg = self.cfg
        act = activation_function_selection(cfg.activation)
        # trace vocabulary (PERF.md section 3): what is not a module gets
        # an explicit scope; each conv is already named `conv_<i>` by flax
        with jax.named_scope("geometry"):
            cargs = self.conv_args(batch)
        # a conv that can trade memory for recomputation does so where its
        # program is differentiated once (evaluation, serving), not in the
        # EF train step, which differentiates twice (models/schnet.CFConv)
        cargs["train"] = train
        x, pos = self.encode(batch, cargs, act, train)
        with jax.named_scope("heads"):
            return self.decode(x, pos, batch, cargs, act, train)

    def encode(self, batch: GraphBatch, cargs, act, train: bool):
        """Conv-stack encoder (reference: Base.py:303-318). Subclasses with
        extra threaded state (PAINN vector channel, MACE irreps) override."""
        cfg = self.cfg
        x = self.initial_node_features(batch, cargs)
        pos = batch.pos
        in_dim = x.shape[-1]
        # sampled giant-graph batches (docs/sampling.md): slots served
        # from the historical-embedding cache are stale constants, not
        # fresh computations — they override each layer's output and are
        # excluded from the batch-norm statistics (their stale scale
        # would skew the running moments the fresh nodes train under)
        stats_mask = batch.node_mask
        if batch.hist_states is not None and batch.hist_mask is not None:
            stats_mask = stats_mask & ~batch.hist_mask
        for i in range(cfg.num_conv_layers):
            conv = self.make_conv(in_dim, cfg.hidden_dim, i,
                                  final=(i == cfg.num_conv_layers - 1))
            if cfg.conv_checkpointing:
                x, pos = _remat_call(conv, x, pos, batch, cargs)
            else:
                x, pos = conv(x, pos, batch, cargs)
            if self.use_batch_norm:
                x = MaskedBatchNorm(name=f"feature_norm_{i}")(
                    x, stats_mask, use_running_average=not train)
            x = act(x)
            if (batch.hist_states is not None
                    and i < cfg.num_conv_layers - 1):
                x = jnp.where(batch.hist_mask[:, None],
                              batch.hist_states[i], x)
            # fresh post-layer states for the historical-cache refresh
            # (train_step.make_sampled_train_step applies them with
            # "intermediates" mutable; a no-op sow otherwise)
            self.sow("intermediates", f"encoder_h{i}", x)
            in_dim = cfg.hidden_dim
        return x, pos

    def decode(self, x, pos, batch: GraphBatch, cargs, act, train: bool):
        """Multihead decoder (reference: Base.py:320-347)."""
        cfg = self.cfg
        num_graphs = batch.num_graphs
        x_graph = global_mean_pool(x, batch.node_graph, num_graphs, batch.node_mask)

        graph_heads = [h for h in cfg.heads if h.head_type == "graph"]
        shared = None
        if graph_heads:
            g0 = graph_heads[0]
            shared = MLP([g0.dim_sharedlayers] * g0.num_sharedlayers,
                         activation=act, activate_final=True,
                         name="graph_shared")(x_graph)

        outputs: List[jnp.ndarray] = []
        outputs_var: List[jnp.ndarray] = []
        widen = 1 + cfg.var_output
        for ih, head in enumerate(cfg.heads):
            if head.head_type == "graph":
                dims = list(head.dim_headlayers) + [head.output_dim * widen]
                out = MLP(dims, activation=act, name=f"head_{ih}")(shared)
            elif head.node_arch in ("mlp", "mlp_per_node"):
                idx = None
                if head.node_arch == "mlp_per_node":
                    idx = node_index_in_graph(batch.node_graph, num_graphs)
                out = MLPNode(
                    hidden_dims=head.dim_headlayers,
                    output_dim=head.output_dim * widen,
                    num_nodes=max(cfg.num_nodes, 1),
                    node_type=head.node_arch,
                    activation=act,
                    name=f"head_{ih}")(x, idx)
            elif head.node_arch == "conv":
                # conv-type node head: fresh convs of the same stack type
                # (reference: Base.py:262-290 _init_node_conv + forward :334-341)
                h, hpos = x, pos
                if "vec_channel_encoder" in cargs:
                    # vector-channel stacks: every conv head starts from
                    # the ENCODER's final v, not the previous head's
                    cargs["vec_channel"] = cargs["vec_channel_encoder"]
                # Every head conv gets batchnorm + activation (the
                # reference creates BatchNorm1d for conv heads in EVERY
                # stack, _init_node_conv Base.py:240-260 — use_batch_norm
                # only governs encoder feature layers; without the BN the
                # unnormalized stacks EGNN/PAINN/PNAEq/DimeNet explode
                # through the head convs), and a per-node Dense makes the
                # output projection.
                # INTENTIONAL DIVERGENCE: the reference's LAST head conv
                # maps straight to output_dim and its output is ALSO
                # BN+relu'd (forward, Base.py:336-341) — a relu-ranged,
                # batch-renormalized regression output. On this port that
                # trained to the graph-mean floor for entire model
                # families (r4 ablations at the 40-epoch probe: BN+act
                # final — MFC 0.43 RMSE, worse than predicting the mean;
                # BN-only final — GIN/PNAEq pinned at the 0.267 floor by
                # the BN-scale-collapse attractor, where shrinking the
                # output BN's scale beats extracting signal; linear final
                # — PNAEq 0.63, its conv output unbounded without the
                # norm). Keeping all convs hidden-layer-like (BN + act)
                # and projecting with a linear Dense has none of those
                # attractors: every conv-head model either matched or
                # beat its best previous variant.
                hdims = list(head.dim_headlayers)
                hin = h.shape[-1]
                for li, hd in enumerate(hdims):
                    conv = self.make_conv(hin, hd, cfg.num_conv_layers + 100 * ih + li,
                                          final=(li == len(hdims) - 1))
                    h, hpos = conv(h, hpos, batch, cargs)
                    h = MaskedBatchNorm(name=f"head_{ih}_norm_{li}")(
                        h, batch.node_mask, use_running_average=not train)
                    h = act(h)
                    hin = hd
                out = nn.Dense(head.output_dim * widen,
                               name=f"head_{ih}_out")(h)
            else:
                raise ValueError(f"unknown node head type {head.node_arch}")
            outputs.append(out[..., :head.output_dim])
            if cfg.var_output:
                outputs_var.append(out[..., head.output_dim:] ** 2)
        if cfg.var_output:
            return outputs, outputs_var
        return outputs, None
