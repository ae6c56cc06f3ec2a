"""Knowledge-graph embedding models: ComplEx and RESCAL.

Reference apps/knowledge_graph_embeddings.cc (ComplEx score/grad :832-858,
RESCAL :860-907, AdaGrad :415-435, negative sampling via PullSample
:452-465). Here the scoring functions are pure JAX on *batches* of triples,
so score + grad + update fuse into one XLA program (ops/fused.py) instead of
the reference's per-triple loop.

Embedding layout: an entity row holds a complex vector of dimension `dim` as
[re | im] (2*dim floats); ComplEx relations are the same; RESCAL relations
are a real dim x dim matrix (dim^2 floats). The stored value row additionally
carries the AdaGrad accumulator (ops/fused.py layout [emb | acc]).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.fused import decode_place


def complex_score(s: jnp.ndarray, r: jnp.ndarray,
                  o: jnp.ndarray) -> jnp.ndarray:
    """Re(<s, r, conj(o)>) for [..., 2d] embeddings (kge.cc ComplEx)."""
    d = s.shape[-1] // 2
    sr, si = s[..., :d], s[..., d:]
    rr, ri = r[..., :d], r[..., d:]
    orr, oi = o[..., :d], o[..., d:]
    return (sr * rr * orr + si * rr * oi
            + sr * ri * oi - si * ri * orr).sum(-1)


def rescal_score(s: jnp.ndarray, r: jnp.ndarray,
                 o: jnp.ndarray) -> jnp.ndarray:
    """s^T R o with R = r reshaped to [d, d] (kge.cc RESCAL)."""
    d = s.shape[-1]
    R = r.reshape(r.shape[:-1] + (d, d))
    return jnp.einsum("...i,...ij,...j->...", s, R, o)


def _nll_loss(pos: jnp.ndarray, neg_s: jnp.ndarray, neg_o: jnp.ndarray,
              self_adv_temp: float = 0.0) -> jnp.ndarray:
    """Negative-sampling logistic loss: -log sig(pos) - sum log sig(-neg)
    (the reference trains with sigmoid loss over neg_ratio negatives per
    side, kge.cc train loop :437-531).

    self_adv_temp > 0 switches the negative term to SELF-ADVERSARIAL
    weighting (Sun et al. 2019, RotatE eq. 5): each negative is weighted
    by softmax(temp * score) with a stopped gradient, so the hardest
    negatives in the batch dominate the update. This addresses the
    mid-scale failure of uniform negatives (at 14k entities uniform
    draws almost never hit the runner-up entities that carry the
    signal; tests/test_apps.py test_kge_midscale_levers_beat_uniform)."""
    pos_l = jax.nn.softplus(-pos)
    if self_adv_temp > 0.0:
        ws = jax.nn.softmax(
            self_adv_temp * jax.lax.stop_gradient(neg_s), axis=-1)
        wo = jax.nn.softmax(
            self_adv_temp * jax.lax.stop_gradient(neg_o), axis=-1)
        neg_l = (ws * jax.nn.softplus(neg_s)).sum(-1) \
            + (wo * jax.nn.softplus(neg_o)).sum(-1)
    else:
        neg_l = jax.nn.softplus(neg_s).sum(-1) \
            + jax.nn.softplus(neg_o).sum(-1)
    return (pos_l + neg_l).mean()


def make_kge_loss(model: str = "complex", self_adv_temp: float = 0.0,
                  l2: float = 0.0):
    """loss_fn for ops/fused.py. Roles: s, r, o [B, *]; neg [B, N] entity
    embeddings used to corrupt both the subject and the object side.
    `self_adv_temp` enables self-adversarial negative weighting (see
    _nll_loss).

    `l2` > 0 adds per-batch (lazy) L2 on the POSITIVE triple's embedding
    rows — the ComplEx paper's regularizer, absent in the reference's
    sigmoid-loss trainer (kge.cc :437-531) but load-bearing once train
    coverage of the (s, r) pair space is sparse: unregularized NS-SGD
    then memorizes train triples (loss falls) while test ranking stays
    random (seen at 14.5k entities). Lazy = only rows
    touched by the step decay, which is exactly AdaGrad-compatible."""
    score = {"complex": complex_score, "rescal": rescal_score}[model]

    def loss_fn(embs, aux):
        s, r, o, neg = embs["s"], embs["r"], embs["o"], embs["neg"]
        pos = score(s, r, o)
        # corrupt subject and object with the same negative pool
        neg_s = score(neg, r[:, None, :], o[:, None, :])
        neg_o = score(s[:, None, :], r[:, None, :], neg)
        loss = _nll_loss(pos, neg_s, neg_o, self_adv_temp)
        if l2 > 0.0:
            loss = loss + l2 * ((s * s).sum(-1) + (r * r).sum(-1)
                                + (o * o).sum(-1)).mean()
        return loss

    return loss_fn


def complex_eval_scores(ent: jnp.ndarray, rel: jnp.ndarray,
                        s: jnp.ndarray, r: jnp.ndarray,
                        o: jnp.ndarray) -> jnp.ndarray:
    """All-entity scores for filtered-MRR eval (kge.cc Evaluator :544-775):
    given full entity matrix [E, 2d] and a triple batch, return
    (scores_o [B, E] for object prediction, scores_s [B, E] for subject).
    One matmul per side -> MXU-friendly."""
    d = ent.shape[-1] // 2
    er, ei = ent[..., :d], ent[..., d:]
    sr, si = s[..., :d], s[..., d:]
    rr, ri = r[..., :d], r[..., d:]
    # object prediction: Re(<s, r, conj(e)>) for all e
    a = sr * rr - si * ri   # coefficient of e_re
    b = sr * ri + si * rr   # coefficient of e_im
    scores_o = a @ er.T + b @ ei.T
    # subject prediction: Re(<e, r, conj(o)>) for all e
    orr, oi = o[..., :d], o[..., d:]
    c = rr * orr + ri * oi
    dcoef = rr * oi - ri * orr
    scores_s = c @ er.T + dcoef @ ei.T
    return scores_o, scores_s


def rescal_eval_scores(ent: jnp.ndarray, rel: jnp.ndarray,
                       s: jnp.ndarray, r: jnp.ndarray,
                       o: jnp.ndarray) -> jnp.ndarray:
    """All-entity RESCAL scores s^T R e (object side) and e^T R o (subject
    side) as two matmuls against the full entity matrix [E, d]."""
    d = ent.shape[-1]
    R = r.reshape(r.shape[:-1] + (d, d))
    sR = jnp.einsum("bi,bij->bj", s, R)      # [B, d]
    Ro = jnp.einsum("bij,bj->bi", R, o)      # [B, d]
    return sR @ ent.T, Ro @ ent.T


def make_eval_scores(model: str):
    return {"complex": complex_eval_scores,
            "rescal": rescal_eval_scores}[model]


def score_numpy(model: str, s, r, o):
    """Host-side scoring of a handful of (s, r, o) rows — used for the
    filtered-rank correction, whose per-batch filter sets are tiny."""
    import numpy as np
    s, r, o = (np.asarray(x, dtype=np.float64) for x in (s, r, o))
    if model == "complex":
        d = s.shape[-1] // 2
        sr, si = s[..., :d], s[..., d:]
        rr, ri = r[..., :d], r[..., d:]
        orr, oi = o[..., :d], o[..., d:]
        return (sr * rr * orr + si * rr * oi
                + sr * ri * oi - si * ri * orr).sum(-1)
    d = s.shape[-1]
    R = r.reshape(r.shape[:-1] + (d, d))
    return np.einsum("...i,...ij,...j->...", s, R, o)


def make_true_score(model: str):
    """True-triple scores from query ROWS, as its own tiny executable.

    Kept separate from the candidate-count scan on purpose: in the
    candidate-partitioned multi-process eval every rank compiles a counts
    program with a DIFFERENT tile count (its owned-entity share), and the
    comparisons `candidate > true` must use byte-identical true scores on
    every rank — a shared, shape-identical executable guarantees that;
    a subgraph inside differently-shaped programs does not."""
    score = {"complex": complex_score, "rescal": rescal_score}[model]

    # apm-lint: disable=APM008 model-math eval program over already-
    # gathered rows: backend-generic jax compute, no pool donation and no
    # sharded dispatch — the PM data plane proper rides the DevicePort
    @jax.jit
    def fn(se, re_, oe):
        return score(se, re_, oe)

    return fn


def _pool_rows(pool, tables, keys, dim: int):
    """The first `dim` columns of the main-pool rows of `keys`, routed
    as the fused step routes them: ONE look-up of the key's place word
    in the router's mirror (`tables`: `DeviceRouter.tables()`), split
    into shard and slot for this pool (`ops/fused.py decode_place`)."""
    sh, sl = decode_place(tables[0][keys], pool.shape[1])
    return pool[sh, sl, :dim]


def make_pool_eval_counts_mp(model: str, ent_dim: int, rel_dim: int,
                             chunk: int):
    """Candidate-partitioned twin of make_pool_eval_counts (VERDICT r4
    item 5 — multi-process chunked eval). Differences:

      - query embeddings arrive as ROWS (se/re_/oe, fetched via
        Server.read_main, which resolves remote owners over the DCN
        channel) instead of keys, so the program only gathers CANDIDATE
        rows — which are exactly this rank's owned entities, always in
        the local pool;
      - `ent_keys` tiles cover the rank's OWNED entities only, padded at
        the tail (`nvalid` masks the padding); each entity has exactly
        one owner, so N ranks partition the candidate set exactly and
        the per-rank greater-counts allreduce-SUM to the global counts
        (reference distributed Evaluator, kge.cc:544-775);
      - the true score is an INPUT (make_true_score), identical bytes on
        every rank.

    fn(ent_main, tables, ent_keys [nch, chunk], nvalid, se, re_, oe,
       skeys [B], okeys [B], true_sc [B]) -> (greater_o [B],
       greater_s [B])."""
    scores_fn = make_eval_scores(model)

    # apm-lint: disable=APM008 chunked eval-count program (model math
    # over the shared pool mirror): backend-generic jax, not a PM
    # data-plane dispatch site
    @jax.jit
    def counts(ent_main, tables, ent_keys, nvalid, se, re_, oe, skeys,
               okeys, true_sc):
        def ent_rows(keys):
            return _pool_rows(ent_main, tables, keys, ent_dim)

        C = ent_keys.shape[1]

        def body(carry, xs):
            g_o, g_s = carry
            keys, start = xs
            # barrier: see make_pool_eval_counts (blocks the whole-pool
            # bf16 convert hoist at north-star scale)
            rows = jax.lax.optimization_barrier(
                ent_rows(keys))                          # [C, d]
            so, ss = scores_fn(rows, None, se, re_, oe)  # [B, C] each
            mask = (start + jnp.arange(C)) < nvalid
            # exclude the true entity BY KEY (see make_pool_eval_counts)
            m_o = mask[None, :] & (keys[None, :] != okeys[:, None])
            m_s = mask[None, :] & (keys[None, :] != skeys[:, None])
            g_o = g_o + ((so > true_sc[:, None]) & m_o).sum(
                axis=1, dtype=jnp.int32)
            g_s = g_s + ((ss > true_sc[:, None]) & m_s).sum(
                axis=1, dtype=jnp.int32)
            return (g_o, g_s), None

        B = skeys.shape[0]
        z = jnp.zeros(B, jnp.int32)
        starts = jnp.arange(ent_keys.shape[0]) * C
        (g_o, g_s), _ = jax.lax.scan(body, (z, z), (ent_keys, starts))
        return g_o, g_s

    return counts


def make_pool_eval_counts(model: str, ent_dim: int, rel_dim: int,
                          chunk: int, shared_pool: bool = False):
    """Full-entity eval WITHOUT materializing the entity matrix: candidate
    rows are gathered straight from the sharded main POOL in [B, chunk]
    tiles under a lax.scan (VERDICT r3 item 4 — at Wikidata5M scale the
    old evaluate() shipped ~1.2 GiB of scores to the host per batch of 64
    and needed a 4.7 GB host entity matrix; reference Evaluator
    kge.cc:544-775 loops candidates per triple).

    Returns fn(ent_main, rel_main, tables, ent_keys [nch, chunk] (key
    OOB-padded), nE, skeys [B], rkeys [B], okeys [B]) ->
    (greater_o [B], greater_s [B], true_sc [B]): for each side, the
    number of real candidates scoring strictly above the true triple.
    Filtered-rank correction happens on the host over the (tiny)
    per-triple filter sets (apps/.. evaluate).

    shared_pool=True drops the rel_main parameter and reads relation rows
    from ent_main — REQUIRED at north-star scale when entities and
    relations share one length class: the AOT compiler accounts each
    program parameter's HBM separately even when the caller passes the
    same buffer twice, so an 8.8 GiB pool passed as both ent_main and
    rel_main is budgeted at 17.6 GiB and the compile is rejected before
    any real allocation happens (observed on v5e at 4.6M entities)."""
    score = {"complex": complex_score, "rescal": rescal_score}[model]
    scores_fn = make_eval_scores(model)

    # apm-lint: disable=APM008 pool-eval count program (model math):
    # backend-generic jax, not a PM data-plane dispatch site
    @jax.jit
    def counts(ent_main, rel_main, tables, ent_keys, nE, skeys, rkeys,
               okeys):
        def ent_rows(keys):
            return _pool_rows(ent_main, tables, keys, ent_dim)

        se = ent_rows(skeys)
        oe = ent_rows(okeys)
        re_ = _pool_rows(ent_main if shared_pool else rel_main, tables,
                         rkeys, rel_dim)
        true_sc = score(se, re_, oe)  # same triple -> same score each side

        C = ent_keys.shape[1]

        def body(carry, xs):
            g_o, g_s = carry
            keys, start = xs
            # the barrier pins the gathered tile: without it XLA commutes
            # the matmul's bf16 convert across the gather and hoists it
            # out of the scan as convert(whole pool) — a pool-sized HLO
            # temp (4.47 GiB at Wikidata5M scale, compile-time OOM)
            rows = jax.lax.optimization_barrier(
                ent_rows(keys))                        # [C, d]
            so, ss = scores_fn(rows, None, se, re_, oe)  # [B, C] each
            mask = (start + jnp.arange(C)) < nE
            # exclude the true entity BY KEY, not by score comparison:
            # the candidate matmul form rounds differently from the
            # direct true-score form, so the true entity could otherwise
            # count itself as "greater" by an ulp
            m_o = mask[None, :] & (keys[None, :] != okeys[:, None])
            m_s = mask[None, :] & (keys[None, :] != skeys[:, None])
            g_o = g_o + ((so > true_sc[:, None]) & m_o).sum(
                axis=1, dtype=jnp.int32)
            g_s = g_s + ((ss > true_sc[:, None]) & m_s).sum(
                axis=1, dtype=jnp.int32)
            return (g_o, g_s), None

        B = skeys.shape[0]
        z = jnp.zeros(B, jnp.int32)
        starts = jnp.arange(ent_keys.shape[0]) * C
        (g_o, g_s), _ = jax.lax.scan(body, (z, z), (ent_keys, starts))
        return g_o, g_s, true_sc

    if shared_pool:
        def counts_shared(ent_main, tables, ent_keys, nE, skeys, rkeys,
                          okeys):
            return counts(ent_main, None, tables, ent_keys, nE, skeys,
                          rkeys, okeys)
        return counts_shared
    return counts
