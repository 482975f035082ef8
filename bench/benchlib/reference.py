"""Plain reference of the federated job the program runs: HiCS-FL
(Algorithm 1) with FedAvg clients, written from the paper and the
configuration file and importing nothing of the program.  The model is
the configuration's family (``families/<family>.py``): its
``init_params``, ``loss`` and ``head_update``.

One round t of a job of T rounds, from the global parameters θ, the
server's Δb buffer (N, C) and the set of clients seen so far:

1. Selection.  While some client is unseen (coverage sweep, Alg. 1
   lines 14-15): the K largest of Gumbel(key) + 10⁶·[unseen].  After
   that: Ĥ = H(softmax(Δb/τ)) per client (Eq. 7), the Eq. 9 distance
   arccos(cos(Δb_u, Δb_k)) + λ|Ĥ_u − Ĥ_k|, Ward agglomerative
   clustering into M = K groups (merge order: first minimum in
   row-major order; labels numbered by each cluster's smallest member),
   and K two-stage draws without replacement (Eq. 10): a cluster by
   Gumbel argmax of γ_t·H̄_m over clusters with clients left, γ_t =
   γ₀(1 − t/T), then a client by Gumbel argmax of log p_k inside it.
2. Local update of each selected client: ``epochs`` passes of
   mini-batch SGD over a fresh permutation of its padded rows (the
   first ⌊cap/B⌋·B), the family's masked mean loss, a batch with no
   real row leaves the parameters as they are; step size
   lr·0.5^⌊t/10⌋.
3. Aggregation θ ← mean of the K local parameters; Δb rows of the K
   clients ← the family's head update (a classifier's: b_k − b, the
   output layer's bias update).

Steps 2 and 3 scan over blocks of the cohort, vmapped inside a block,
and the mean is the blocks' running sum over K.  A block is the whole
cohort unless the job sets ``cohort_block``: a reference at published
widths then holds one block of parameter copies, not K.  Only the order
of that sum differs.

The keys follow one chain, as the program's scanned driver draws them:
``rng, k0 = split(PRNGKey(seed))`` initialises θ, then each round
``rng, kr = split(rng)``, ``k_sel, k_loc = split(kr)``, and client i of
the cohort trains with ``split(k_loc, K)[i]``.

Steps 2 and 3 train the cohorts the program reports, round by round, so
the two runs stay comparable however long they are; step 1 is checked
where its input is known exactly: at the first round of a call, from
the Δb and seen set the previous call left, and at rounds t > 0 inside
a call, from the state the program's segments left before them
(``select_at``), where γ_t has moved off γ₀.
Everything runs under ``jax.default_matmul_precision("highest")`` in
``dtype`` (float32; bfloat16 is the control): the parameters, and the
rows where they are floating (token ids stay integers).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

#: HiCS-FL's selector constants as the program's selector defaults
#: state them: softmax temperature τ, Eq. 9's λ, the annealing γ₀.
TEMPERATURE, LAM, GAMMA0 = 0.0025, 10.0, 4.0
#: the server's step-size schedule: ×0.5 every 10 rounds of a job
LR_DECAY, LR_DECAY_EVERY = 0.5, 10
#: Eq. 9's cosine is clipped into the open interval, as arccos needs
COS_CLIP = 1e-7
NORM_EPS = 1e-8


@dataclasses.dataclass(frozen=True, eq=False)
class Job:
    """What a round needs besides the state: static sizes and data."""
    cfg: dict                  # the configuration file
    family: Any                # the configuration's family module
    num_clients: int
    num_select: int
    job_rounds: int
    lr: float
    epochs: int
    batch_size: int
    dtype: Any = jnp.float32
    #: clients a block of the cohort trains at once; None: all K
    cohort_block: Optional[int] = None

    def __post_init__(self):
        b = self.block
        if b < 1 or self.num_select % b:
            raise ValueError(f"reference_cohort_block {b} does not divide "
                             f"the cohort of {self.num_select}")

    @property
    def block(self) -> int:
        return self.cohort_block or self.num_select


def entropy(v, temperature=TEMPERATURE):
    """Ĥ = H(softmax(v/τ)) along the last axis, in float32."""
    u = v.astype(jnp.float32) / temperature
    u = u - jnp.max(u, axis=-1, keepdims=True)
    p = jax.nn.softmax(u, axis=-1)
    return -jnp.sum(p * jax.nn.log_softmax(u, axis=-1), axis=-1)


def distance(delta_b, temperature=TEMPERATURE, lam=LAM,
             gram_dtype=jnp.float32):
    """Eq. 9 over all clients, (N, C) -> (N, N), zero diagonal; the
    Gram's operands in ``gram_dtype``, accumulated in float32."""
    x = delta_b.astype(jnp.float32)
    h = entropy(x, temperature)
    n = jnp.maximum(jnp.sqrt(jnp.sum(x * x, axis=-1)), NORM_EPS)
    g = x.astype(gram_dtype)
    cos = jnp.dot(g, g.T, preferred_element_type=jnp.float32) / (
        n[:, None] * n[None, :])
    ang = jnp.arccos(jnp.clip(cos, -1.0 + COS_CLIP, 1.0 - COS_CLIP))
    ang = jnp.where(jnp.eye(x.shape[0], dtype=bool), 0.0, ang)
    return ang + lam * jnp.abs(h[:, None] - h[None, :])


def ward_labels(dist, num_clusters: int):
    """Ward clustering of (N, N) distances into ``num_clusters`` groups.

    Lance-Williams on squared distances; each merge joins the pair at
    the first minimum of the matrix in row-major order into the lower
    index.  Labels number the clusters by their smallest member.

    The matrix is made exactly symmetric first: Eq. 9 is, but on the
    TPU the Gram's two halves round apart (arccos widens that to some
    1e-6 near parallel rows), and a first minimum below the diagonal would
    merge into the higher index and number that cluster otherwise."""
    n = dist.shape[0]
    dist = dist.astype(jnp.float32)
    d = jnp.where(jnp.eye(n, dtype=bool), jnp.inf,
                  jnp.square(0.5 * (dist + dist.T)))

    def merge(_, carry):
        d, size, rep = carry
        flat = jnp.argmin(d)
        i, j = flat // n, flat % n
        si, sj, dij = size[i], size[j], d[i, j]
        row = ((si + size) * d[i] + (sj + size) * d[j]
               - size * dij) / (si + sj + size)
        row = row.at[i].set(jnp.inf).at[j].set(jnp.inf)
        d = d.at[i, :].set(row).at[:, i].set(row)
        d = d.at[j, :].set(jnp.inf).at[:, j].set(jnp.inf)
        size = size.at[i].add(sj).at[j].set(0.0)
        rep = jnp.where(rep == j, i, rep)
        return d, size, rep

    _, _, rep = jax.lax.fori_loop(
        0, n - num_clusters, merge,
        (d, jnp.ones(n, jnp.float32), jnp.arange(n)))
    is_rep = jnp.zeros(n, bool).at[rep].set(True)
    rank = jnp.cumsum(is_rep) - 1
    return rank[rep].astype(jnp.int32)


def two_stage_sample(key, labels, ent, weights, k: int, gamma):
    """Eq. 10: K draws without replacement, cluster then client."""
    n = labels.shape[0]
    m = k
    counts = jnp.zeros(m).at[labels].add(1.0)
    means = jnp.zeros(m).at[labels].add(ent) / jnp.maximum(counts, 1.0)
    means = jnp.where(counts > 0, means, 0.0)
    logw = jnp.log(jnp.maximum(weights, 1e-30)).astype(jnp.float32)

    def draw(i, carry):
        left, chosen, key = carry
        key, kc, kj = jax.random.split(key, 3)
        alive = jnp.zeros(m).at[labels].add(left.astype(jnp.float32)) > 0
        c = jnp.argmax(jnp.where(alive, gamma * means, -jnp.inf)
                       + jax.random.gumbel(kc, (m,), jnp.float32))
        inside = (labels == c) & left
        j = jnp.argmax(jnp.where(inside, logw, -jnp.inf)
                       + jax.random.gumbel(kj, (n,), jnp.float32))
        return left.at[j].set(False), chosen.at[i].set(j), key

    _, chosen, _ = jax.lax.fori_loop(
        0, k, draw, (jnp.ones(n, bool), jnp.zeros(k, jnp.int32), key))
    return chosen


def coverage_sample(key, seen, k: int):
    g = jax.random.gumbel(key, seen.shape, jnp.float32)
    return jax.lax.top_k(g + jnp.where(seen, 0.0, 1e6), k)[1]


def local_sgd(job: Job, params, x, y, m, key, lr_scale):
    """One client's local epochs; returns (params, mean batch loss)."""
    s = x.shape[0]
    bs = min(job.batch_size, s)
    nb = max(1, s // bs)
    used = nb * bs
    step_size = (job.lr * lr_scale).astype(job.dtype)

    def loss_fn(p, xb, yb, mb):
        return job.family.loss(job.cfg, p, xb, yb, mb)

    def batches(a, perm):
        return a[perm].reshape(nb, bs, *a.shape[1:])

    def epoch(p, ekey):
        perm = jax.random.permutation(ekey, s)[:used]
        xb, yb, mb = batches(x, perm), batches(y, perm), batches(m, perm)

        def step(p, batch):
            xi, yi, mi = batch
            loss, g = jax.value_and_grad(loss_fn)(p, xi, yi, mi)
            live = (jnp.sum(mi) > 0).astype(job.dtype)
            p = jax.tree_util.tree_map(
                lambda a, ga: a - step_size * (ga * live), p, g)
            return p, loss

        p, losses = jax.lax.scan(step, p, (xb, yb, mb))
        return p, jnp.mean(losses)

    params, epoch_losses = jax.lax.scan(
        epoch, params, jax.random.split(key, job.epochs))
    return params, jnp.mean(epoch_losses)


def _select(job: Job, delta_b, seen, weights, t, key):
    k = job.num_select

    def sweep(_):
        return coverage_sample(key, seen, k)

    def clustered(_):
        ent = entropy(delta_b)
        labels = ward_labels(distance(delta_b, gram_dtype=job.dtype), k)
        gamma = GAMMA0 * jnp.maximum(
            0.0, 1.0 - t / jnp.maximum(1.0, float(job.job_rounds)))
        return two_stage_sample(key, labels, ent, weights, k, gamma)

    return jax.lax.cond(jnp.any(~seen), sweep, clustered, 0)


def _round(job: Job, x, y, m, carry, inp):
    params, delta_b, rng = carry
    t, ids = inp
    rng, kr = jax.random.split(rng)
    _, k_loc = jax.random.split(kr)
    lr_scale = jnp.float32(LR_DECAY) ** (t // LR_DECAY_EVERY)
    keys = jax.random.split(k_loc, job.num_select)
    local = jax.vmap(lambda xi, yi, mi, ki: local_sgd(
        job, params, xi, yi, mi, ki, lr_scale))
    head_update = functools.partial(job.family.head_update, job.cfg)

    def block(total, inp):
        bids, bkeys = inp
        new, losses = local(x[bids], y[bids], m[bids], bkeys)
        total = jax.tree_util.tree_map(
            lambda s, a: s + jnp.sum(a, axis=0), total, new)
        return total, (head_update(new, params), losses)

    nblk = job.num_select // job.block
    total, (db, losses) = jax.lax.scan(
        block, jax.tree_util.tree_map(jnp.zeros_like, params),
        (ids.reshape(nblk, -1), keys.reshape(nblk, -1, *keys.shape[1:])))
    db = db.reshape(job.num_select, *db.shape[2:])
    losses = losses.reshape(-1)
    params = jax.tree_util.tree_map(lambda s: s / job.num_select, total)
    delta_b = delta_b.at[ids].set(db.astype(delta_b.dtype))
    out = (jnp.mean(losses.astype(jnp.float32)), entropy(delta_b))
    return (params, delta_b, rng), out


@functools.partial(jax.jit, static_argnums=0, donate_argnums=4)
def _call(job: Job, x, y, m, carry, ts, ids):
    """Rounds ``ts`` from ``carry``, which it donates: the caller's
    carry is gone once the call is made, and the rounds' carry takes its
    buffers, so no second copy of the parameters is held."""
    with jax.default_matmul_precision("highest"):
        return jax.lax.scan(functools.partial(_round, job, x, y, m), carry,
                            (ts, ids))


def select_at(job: Job, delta_b, seen, weights, rng,
              t: int = 0) -> np.ndarray:
    """The selection of round ``t`` of a job whose key chain stood at
    ``rng`` before its round 0, from the state (Δb, seen) that round
    starts from."""
    for _ in range(t):
        rng, _ = jax.random.split(rng)
    _, kr = jax.random.split(rng)
    k_sel, _ = jax.random.split(kr)
    with jax.default_matmul_precision("highest"):
        ids = jax.jit(_select, static_argnums=0)(
            job, jnp.asarray(delta_b, jnp.float32), jnp.asarray(seen),
            weights, jnp.int32(t), k_sel)
    return np.asarray(ids)


def start(job: Job, seed: int, num_classes: int):
    """The reference's state before the job's first round."""
    rng, k0 = jax.random.split(jax.random.PRNGKey(seed))
    params = jax.tree_util.tree_map(
        lambda a: a.astype(job.dtype), job.family.init_params(job.cfg, k0))
    return (params, jnp.zeros((job.num_clients, num_classes), job.dtype),
            rng)


def follow_call(job: Job, x, y, m, carry, ids, mid=()):
    """One call of ``job_rounds`` rounds that trains the program's
    (job_rounds, K) cohorts; ``carry`` is donated, so the caller keeps
    the returned one alone.  Returns (carry, per-round loss, Ĥ of every
    client after each round, {t: Δb before round t} for each t in
    ``mid``)."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(job.dtype)
    ids = jnp.asarray(ids, jnp.int32)
    cuts = [0, *sorted(mid), ids.shape[0]]
    losses, ents, mid_db = [], [], {}
    for a, b in zip(cuts[:-1], cuts[1:]):
        if a:
            mid_db[a] = np.asarray(carry[1], np.float64)
        carry, (loss, ent) = _call(job, x, y, m, carry,
                                   jnp.arange(a, b, dtype=jnp.int32),
                                   ids[a:b])
        losses.append(np.asarray(loss))
        ents.append(np.asarray(ent))
    return carry, np.concatenate(losses), np.concatenate(ents), mid_db


_DISTANCE = jax.jit(distance)
_DISTANCE_BF16_GRAM = jax.jit(functools.partial(distance,
                                                gram_dtype=jnp.bfloat16))


def reference_distance(delta_b) -> np.ndarray:
    """Eq. 9 of a host (N, C) Δb, float32 throughout."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(_DISTANCE(jnp.asarray(delta_b, jnp.float32)))


def control_distance(delta_b) -> np.ndarray:
    """Eq. 9 with the Gram's operands in bfloat16: the control's."""
    with jax.default_matmul_precision("highest"):
        return np.asarray(_DISTANCE_BF16_GRAM(
            jnp.asarray(delta_b, jnp.float32)))
