"""Config dataclasses for every architecture family of the training stack.

Everything is a frozen dataclass so configs hash/compare cleanly and can be
used as static args to jit. Each assigned architecture gets one module in
``repro/configs`` exposing ``ARCH`` (an :class:`ArchConfig`); the registry
resolves ``--arch <id>`` strings to those objects.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# model-family configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """GShard-style top-k routed MoE with optional shared experts."""

    n_experts: int
    top_k: int
    d_expert: int                 # hidden width of each routed expert
    n_shared_experts: int = 0
    d_shared: int = 0             # total hidden width of the shared expert(s)
    capacity_factor: float = 1.25
    router_aux_weight: float = 1e-3
    # experts are sharded over the `model` mesh axis; pad count up to a
    # multiple of the axis size so the expert dim shards evenly.
    def padded_experts(self, ep: int) -> int:
        return ((self.n_experts + ep - 1) // ep) * ep


@dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    qk_norm: bool = False
    qkv_bias: bool = False
    attention: str = "full"       # full | sliding | chunked_global
    window: int = 0               # sliding window size / local chunk size
    global_every: int = 0         # chunked_global: every k-th layer is global
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    moe: Optional[MoEConfig] = None
    dtype: str = "bfloat16"
    remat: bool = True

    # -- derived ---------------------------------------------------------
    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    def n_params(self) -> int:
        """Total parameter count (embeddings included)."""
        d, L = self.d_model, self.n_layers
        attn = d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * d
        if self.moe is None:
            ffn = 3 * d * self.d_ff
        else:
            m = self.moe
            ffn = m.n_experts * 3 * d * m.d_expert + 3 * d * m.d_shared
            ffn += d * m.n_experts  # router
        norms = 2 * d
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return L * (attn + ffn + norms) + emb + d

    def n_active_params(self) -> int:
        """Parameters touched per token (MoE counts only routed top-k)."""
        if self.moe is None:
            return self.n_params()
        d, L, m = self.d_model, self.n_layers, self.moe
        attn = d * (self.q_dim + 2 * self.kv_dim) + self.q_dim * d
        ffn = m.top_k * 3 * d * m.d_expert + 3 * d * m.d_shared + d * m.n_experts
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return L * (attn + ffn + 2 * d) + emb + d

    def scaled(self, **kw) -> "LMConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class GNNConfig:
    name: str
    n_layers: int = 2
    d_hidden: int = 128
    aggregator: str = "mean"        # mean | max | sum
    sample_sizes: Tuple[int, ...] = (25, 10)
    n_classes: int = 41             # reddit has 41 classes
    pq_features: bool = False       # beyond-paper: PQ-compressed feature store
    dtype: str = "float32"

    def scaled(self, **kw) -> "GNNConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class RecsysConfig:
    name: str
    kind: str                       # dlrm | dcnv2 | sasrec | widedeep
    embed_dim: int
    vocab_sizes: Tuple[int, ...]    # rows per sparse table
    n_dense: int = 0
    multi_hot: int = 1              # lookups per field (EmbeddingBag bag size)
    bot_mlp: Tuple[int, ...] = ()
    top_mlp: Tuple[int, ...] = ()
    mlp: Tuple[int, ...] = ()
    n_cross_layers: int = 0
    # sasrec
    seq_len: int = 0
    n_blocks: int = 0
    n_heads: int = 0
    interaction: str = "dot"        # dot | cross | concat | self-attn-seq
    dtype: str = "float32"

    @property
    def n_sparse(self) -> int:
        return len(self.vocab_sizes)

    def n_embedding_rows(self) -> int:
        return sum(self.vocab_sizes)

    def scaled(self, **kw) -> "RecsysConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell. `kind` selects which step function is lowered."""

    name: str
    kind: str
    # lm
    seq_len: int = 0
    global_batch: int = 0
    # gnn
    n_nodes: int = 0
    n_edges: int = 0
    d_feat: int = 0
    batch_nodes: int = 0
    fanout: Tuple[int, ...] = ()
    batch_graphs: int = 0
    # recsys
    batch: int = 0
    n_candidates: int = 0


# canonical LM shape set (assigned to every LM arch)
LM_SHAPES = (
    ShapeConfig("train_4k", "lm_train", seq_len=4096, global_batch=256),
    ShapeConfig("prefill_32k", "lm_prefill", seq_len=32768, global_batch=32),
    ShapeConfig("decode_32k", "lm_decode", seq_len=32768, global_batch=128),
    ShapeConfig("long_500k", "lm_decode", seq_len=524288, global_batch=1),
)

GNN_SHAPES = (
    ShapeConfig("full_graph_sm", "gnn_full", n_nodes=2708, n_edges=10556, d_feat=1433),
    ShapeConfig("minibatch_lg", "gnn_minibatch", n_nodes=232965, n_edges=114615892,
                batch_nodes=1024, fanout=(15, 10), d_feat=602),
    ShapeConfig("ogb_products", "gnn_full", n_nodes=2449029, n_edges=61859140, d_feat=100),
    ShapeConfig("molecule", "gnn_batched", n_nodes=30, n_edges=64, batch_graphs=128,
                d_feat=64),
)

REC_SHAPES = (
    ShapeConfig("train_batch", "rec_train", batch=65536),
    ShapeConfig("serve_p99", "rec_serve", batch=512),
    ShapeConfig("serve_bulk", "rec_serve", batch=262144),
    ShapeConfig("retrieval_cand", "rec_retrieval", batch=1, n_candidates=1_000_000),
)


# ---------------------------------------------------------------------------
# arch container
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ArchConfig:
    arch_id: str
    family: str                     # lm | gnn | recsys
    model: object
    shapes: Tuple[ShapeConfig, ...]
    skip_shapes: Tuple[str, ...] = ()
    skip_reason: str = ""
    source: str = ""

    def shape(self, name: str) -> ShapeConfig:
        for s in self.shapes:
            if s.name == name:
                return s
        raise KeyError(f"{self.arch_id} has no shape {name!r}")

    def active_shapes(self) -> Tuple[ShapeConfig, ...]:
        return tuple(s for s in self.shapes if s.name not in self.skip_shapes)
