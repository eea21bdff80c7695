"""Dense encoders + cross-encoder re-ranker heads: the bridge between the
assigned model architectures and the retrieval core.

* ``encode`` — mean-pooled, L2-normalised backbone states -> fixed-size
  dense vectors (the paper's dense-representation path; DPR-style).
* ``cross_encoder_score`` — joint (query ++ doc) scoring with a scalar
  head: the neural re-ranker the paper plugs in via proxy scorers
  (CEDR/MatchZoo role), exposed as a ``ProxyExtractor``-compatible callable.
* ``CrossEncoderReranker`` — the same scorer packaged as a
  ``core.pipeline.Reranker``: the neural final stage of the served
  funnel (``repro.serving.funnel.FunnelPipeline``).
* ``contrastive_loss`` — in-batch-negatives dual-encoder training (the
  DPR objective) so encoders can be *trained* inside this framework.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.base import TransformerConfig
from repro.distributed.sharding import ParallelCtx
from repro.models import transformer as T


def encode(params, tokens: jax.Array, cfg: TransformerConfig,
           ctx: ParallelCtx, out_dim: int | None = None) -> jax.Array:
    """tokens [B, S] -> unit vectors [B, d_model] (mean pool over non-pad)."""
    hidden, _ = T.backbone(params, tokens, cfg, ctx)
    mask = (tokens < cfg.vocab_size)[..., None]
    s = jnp.sum(jnp.where(mask, hidden, 0.0), axis=1)
    v = s / jnp.maximum(jnp.sum(mask, axis=1), 1)
    if out_dim is not None:
        v = v[..., :out_dim]
    return v / jnp.maximum(jnp.linalg.norm(v, axis=-1, keepdims=True), 1e-9)


def cross_encoder_score(params, q_tokens: jax.Array, d_tokens: jax.Array,
                        cfg: TransformerConfig, ctx: ParallelCtx) -> jax.Array:
    """Joint scoring: concat(q, doc) through the backbone, dot the pooled
    state with the first lm_head column as a scalar relevance head."""
    joint = jnp.concatenate([q_tokens, d_tokens], axis=1)
    hidden, _ = T.backbone(params, joint, cfg, ctx)
    pooled = jnp.mean(hidden, axis=1)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])[:, 0]
    return pooled @ head


def make_proxy_scorer(params, cfg: TransformerConfig, ctx: ParallelCtx,
                      doc_tokens: jax.Array) -> Callable:
    """Adapter producing the (q_tokens, cand_ids) -> [B, C] signature the
    retrieval pipeline's ProxyExtractor expects.

    The weights and the document tokens are arguments of the jitted
    scorer, not values it closes over: closed-over arrays would be
    compiled into the TPU executable as constants (gigabytes at a
    published width, and minutes of compile)."""

    @jax.jit
    def score(params, doc_tokens, q_tokens, cand_ids):
        b, c = cand_ids.shape
        docs = doc_tokens[cand_ids]                      # [B, C, L]
        qq = jnp.repeat(q_tokens[:, None, :], c, axis=1)
        flat_q = qq.reshape(b * c, -1)
        flat_d = docs.reshape(b * c, -1)
        return cross_encoder_score(params, flat_q, flat_d, cfg, ctx).reshape(b, c)

    return lambda q_tokens, cand_ids: score(params, doc_tokens, q_tokens,
                                            cand_ids)


class CrossEncoderReranker:
    """Neural re-rank stage: ``cross_encoder_score`` over the candidate
    documents' tokens, packaged as a ``core.pipeline.Reranker``.

    Gathers ``doc_tokens[cand_ids]``, flattens the (query, candidate)
    pairs to one ``[B*C]`` batch through the jitted joint scorer
    (:func:`make_proxy_scorer`'s adapter pattern), masks padded / absent
    candidates (non-finite candidate scores) to ``-inf``, and reorders —
    the funnel's final stage, also usable as ``RetrievalPipeline``'s
    ``final``."""

    def __init__(self, params, cfg: TransformerConfig, ctx: ParallelCtx,
                 doc_tokens: jax.Array):
        self.doc_tokens = jnp.asarray(doc_tokens)
        self._score = make_proxy_scorer(params, cfg, ctx, self.doc_tokens)

    def _masked_scores(self, q_tokens: jax.Array, cands):
        mask = jnp.isfinite(cands.scores)
        # clamp masked ids to row 0 so the gather stays in bounds; their
        # scores are forced to -inf below regardless of what row 0 scores
        ids = jnp.where(mask, cands.indices, 0)
        return jnp.where(mask, self._score(q_tokens, ids), -jnp.inf)

    def warmup(self, q_tokens: jax.Array, cands):
        """Compile (and run once) the scorer for these shapes, so that a
        later timed ``rerank`` measures a compiled run
        (``serving.funnel.FunnelPipeline`` calls this before seeding its
        cost estimate)."""
        return self._masked_scores(q_tokens, cands)

    def rerank(self, q_tokens: jax.Array, cands, keep: int):
        from repro.core.pipeline import _reorder

        return _reorder(cands, self._masked_scores(q_tokens, cands), keep)


def contrastive_loss(params, q_tokens: jax.Array, pos_doc_tokens: jax.Array,
                     cfg: TransformerConfig, ctx: ParallelCtx,
                     temperature: float = 0.05):
    """In-batch-negative dual-encoder loss (DPR): query i's positive is doc
    i; all other docs in the batch are negatives."""
    qv = encode(params, q_tokens, cfg, ctx)
    dv = encode(params, pos_doc_tokens, cfg, ctx)
    logits = (qv @ dv.T) / temperature
    labels = jnp.arange(qv.shape[0])
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    loss = jnp.mean(lse - gold)
    acc = jnp.mean((jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32))
    return loss, {"contrastive": loss, "in_batch_acc": acc}
