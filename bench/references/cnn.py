"""Plain reference of a Fed-RAC job's first rounds for the CNN family, in
float32.

It recomputes what the program's scan-fused dispatch blocks compute, round by
round and member by member, with straightforward ``jax.numpy`` and every
matrix product at the precision ``num`` gives (``Precision.HIGHEST`` for the
reference itself):

* the weights of each cluster level, made from the seed by the CNN
  family's recipe (``PRNGKey(seed + level)``, one ``fold_in`` per layer);
* the in-program sampler's batch indices: a key per round folded from the
  federation's ``program_seed``, a key per member slot folded from it,
  uniform draws over the shard or, at the master level, class-balanced
  draws;
* each member's ``steps_per_round`` SGD steps of the CNN's cross entropy,
  or at a slave level of the KD loss against the master teacher as it
  stood at the start of the round;
* the MAR decision of every member (Eq. 2 round time against its
  cluster's budget, auto-calibrated as the program's set-up does) and the
  mask, weight and bank gain that the buffered policy gives it;
* FedAvg over the contributing members plus the banked updates of earlier
  rounds, discounted by their staleness.

It imports nothing of the program.  It takes from the program only the
cluster membership and each member's admitted data size ``n_eff``: the
output of Procedures 1 and 2 at set-up, which it does not recompute.

``num`` (``bench.check.Numerics``) selects the precision: the reference,
the control, or a planted fault.  A family other than the CNN brings its
own ``bench/references/<family>.py`` with ``init_params`` and ``run``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

GFLOPS_PER_GHZ = 8.0      # Eq. 2's effective operations per cycle
EFFICIENCY = 0.3          # and the achieved share of an edge device's peak
DN = ("NHWC", "HWIO", "NHWC")


def filters(model: dict, level: int) -> tuple:
    s = model["base_width"] * model["alpha"] ** level
    return tuple(max(4, int(round(f * s))) for f in model["filters"])


def init_params(model: dict, level: int, seed: int, dtype=jnp.float32):
    key = jax.random.PRNGKey(seed + level)
    convs, cin = [], model["in_channels"]
    for i, f in enumerate(filters(model, level)):
        w = (jax.random.normal(jax.random.fold_in(key, i), (3, 3, cin, f))
             * math.sqrt(2.0 / (9 * cin)))
        convs.append({"b": jnp.zeros((f,), dtype), "w": w.astype(dtype)})
        cin = f
    w = (jax.random.normal(jax.random.fold_in(key, 99),
                           (cin, model["classes"])) * cin ** -0.5)
    return {"convs": convs,
            "dense": {"b": jnp.zeros((model["classes"],), dtype),
                      "w": w.astype(dtype)}}


def forward(params, x, num):
    for i, p in enumerate(params["convs"]):
        x = lax.conv_general_dilated(x, p["w"], (1, 1), "SAME",
                                     dimension_numbers=DN,
                                     precision=num.precision) + p["b"]
        x = jnp.maximum(x, 0)
        if i % 2 == 1 and min(x.shape[1], x.shape[2]) >= 2:
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
    x = jnp.mean(x, axis=(1, 2))
    return (jnp.dot(x, params["dense"]["w"], precision=num.precision)
            + params["dense"]["b"])


def _log_softmax(z):
    return z - jax.nn.logsumexp(z, axis=-1, keepdims=True)


def step_loss(params, x, y, t_logits, fed, num, kd: bool):
    logits = forward(params, x, num).astype(jnp.float32)
    ce = -jnp.take_along_axis(_log_softmax(logits), y[:, None], -1)[:, 0]
    if not kd:
        return jnp.mean(ce)
    T, a = fed["kd_T"], fed["kd_alpha"]
    lt = _log_softmax(t_logits.astype(jnp.float32) / T)
    ls = _log_softmax(logits / T)
    kl = jnp.sum(jnp.exp(lt) * (lt - ls), axis=-1)
    return jnp.mean(a * ce + (1.0 - a) * T * T * kl)


def member_update(params, xs, ys, mask, t_logits, fed, num, kd: bool):
    """One member's round: ``len(mask)`` SGD steps over ``xs[s]``; returns
    its parameters and its mask-weighted mean loss."""
    keep = max(1, int(xs.shape[1] * num.batch_keep))
    grad = jax.value_and_grad(step_loss)
    lr = jnp.asarray(fed["lr"] * num.lr_scale, num.dtype)
    losses = []
    for s in range(xs.shape[0]):
        loss, g = grad(params, xs[s, :keep], ys[s, :keep],
                       t_logits[s, :keep], fed, num, kd)
        m = mask[s].astype(num.dtype)
        params = jax.tree.map(lambda w, d: (w - lr * m * d).astype(w.dtype),
                              params, g)
        losses.append(loss * mask[s])
    return params, jnp.sum(jnp.stack(losses)) / jnp.maximum(jnp.sum(mask), 1.0)


def _indices(seed: int, r: int, n, cnt, steps: int, batch: int):
    """(C, steps, batch) draws for the members in slots 0..C-1: uniform
    over each shard's ``n`` items, or with per-slot class populations
    ``cnt`` (C, batch), the instance within the slot's class."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), r)
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
        jnp.arange(len(n), dtype=jnp.int32))
    if cnt is None:
        return jax.vmap(lambda k, ni: jax.random.randint(
            k, (steps, batch), 0, ni))(keys, jnp.asarray(n, jnp.int32))
    return jax.vmap(lambda k, c: jax.random.randint(
        k, (steps, batch), 0, c[None, :]))(keys, jnp.asarray(cnt, jnp.int32))


def balanced_slots(y: np.ndarray, classes: int, batch: int):
    """Per batch slot, its class (present classes round-robin in ascending
    order) and that class's population in the shard."""
    counts = np.bincount(y, minlength=classes)
    present = np.flatnonzero(counts > 0)
    if len(present) == 0:
        present = np.arange(1)
    cls = present[np.arange(batch) % len(present)]
    return cls, np.maximum(counts[cls], 1)


class MarBudget:
    """Eq. 2 round times and the per-level MAR budgets of the set-up."""

    def __init__(self, model: dict, fed: dict, table: np.ndarray,
                 n_data: list, levels: int):
        from bench.flops import cnn as counts
        self.fed, self.table = fed, np.asarray(table, np.float64)
        # Eq. 2 prices the convolutions' forward operations per sample
        self.flops = [sum(2.0 * 9 * h * w * ci * co for h, w, ci, co in
                          counts.conv_shapes(model, l))
                      for l in range(levels)]
        self.bytes = [4.0 * counts.param_count(model, l)
                      for l in range(levels)]
        t0 = [self.time(p, 0, n_data[p]) for p in range(len(self.table))]
        base = float(np.percentile(t0, 40)) / fed["kappa"] ** (levels - 1)
        self.mar = [base * fed["kappa"] ** (levels - 1 - l)
                    for l in range(levels)]

    def time(self, pid: int, level: int, n: int) -> float:
        s, r, _ = self.table[pid]
        train = (self.flops[level] * n * self.fed["epochs"]
                 / (s * GFLOPS_PER_GHZ * 1e9 * EFFICIENCY))
        return train + self.bytes[level] * 8.0 / (r * 1e6)

    def decide(self, level: int, members: list, n_eff: dict):
        """(decision per pid, masks (C, S), weights (C,), gains (C,))."""
        fed, S = self.fed, self.fed["steps_per_round"]
        C = len(members)
        masks = np.zeros((C, S), np.float32)
        w = np.zeros(C, np.float32)
        gain = np.zeros(C, np.float32)
        dec = {}
        if fed["mar_policy"] != "buffer":
            raise ValueError(f"MAR policy {fed['mar_policy']!r} is not "
                             "modelled")
        for i, pid in enumerate(members):
            masks[i] = 1.0
            if self.time(pid, level, n_eff[pid]) > self.mar[level]:
                dec[pid] = "banked"
                gain[i] = n_eff[pid] * fed["staleness_discount"]
            else:
                w[i], dec[pid] = n_eff[pid], "active"
        return dec, masks, w, gain


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def run(model: dict, fed: dict, table, shards: list, seed: int,
        rounds: list, num):
    """Replay ``rounds``: a list, one entry per round from round 0, of
    ``{level: (members, n_eff)}`` as the program held them.

    Returns ``(losses, decisions, params)``: per round and level the mean
    loss over contributing members and each member's MAR decision, and per
    round the parameters of every level after it."""
    if fed["schedule"] != "parallel":
        raise ValueError("only the parallel schedule's KD cadence is modelled")
    levels = max(max(r) for r in rounds) + 1
    budget = MarBudget(model, fed, table, [len(s["y"]) for s in shards],
                       levels)
    S, B = fed["steps_per_round"], fed["local_batch"]
    classes = model["classes"]
    params = {l: init_params(model, l, seed, num.dtype)
              for l in range(levels)}
    bank = {l: [] for l in range(levels)}    # (pid, round, n_eff, params)
    upd = {kd: jax.jit(jax.vmap(
        lambda p, x, y, m, t, kd=kd: member_update(p, x, y, m, t, fed, num,
                                                   kd)))
        for kd in (False, True)}
    teach = jax.jit(lambda p, x: jax.vmap(jax.vmap(
        lambda xb: forward(p, xb, num)))(x))
    losses, decisions, after = [], [], []
    for r, layout in enumerate(rounds):
        start = dict(params)              # the teacher is the round-start master
        loss_r, dec_r = {}, {}
        for lvl in sorted(layout):
            members, n_eff = layout[lvl]
            dec, masks, w, gain = budget.decide(lvl, members, n_eff)
            dec_r[lvl] = dec
            balanced = fed["class_balanced"] and lvl == 0
            ys = [shards[p]["y"] for p in members]
            cls = cnt = None
            if balanced:
                cls, cnt = zip(*(balanced_slots(y, classes, B) for y in ys))
                cnt = np.stack(cnt)
            idx = np.asarray(_indices(fed["program_seed"], r,
                                      [len(y) for y in ys], cnt, S, B))
            if balanced:
                idx = np.stack([_class_rows(ys[i], cls[i], idx[i])
                                for i in range(len(members))])
            train = [i for i in range(len(members)) if masks[i].any()]
            new = {}
            mem_loss = {}
            if train:
                x = jnp.asarray(np.stack([shards[members[i]]["x"][idx[i]]
                                          for i in train]), num.dtype)
                y = jnp.asarray(np.stack([shards[members[i]]["y"][idx[i]]
                                          for i in train]))
                kd = lvl > 0
                t = (teach(start[0], x) if kd
                     else jnp.zeros(x.shape[:3] + (1,), jnp.float32))
                p0 = _stack([params[lvl]] * len(train))
                p1, l1 = upd[kd](p0, x, y, jnp.asarray(masks[train]), t)
                for j, i in enumerate(train):
                    new[i] = jax.tree.map(lambda a, j=j: a[j], p1)
                    mem_loss[i] = float(l1[j])
            ripe = [b for b in bank[lvl] if b[1] < r]
            bank[lvl] = [b for b in bank[lvl] if b[1] >= r]
            us = [b[2] * fed["staleness_discount"] ** max(1, r - b[1])
                  for b in ripe]
            live = float(w.sum()) > 0.0
            if live:
                # float32 weights over the float32 total, the members' sum
                # first and the bank's added to it
                total = np.float32(w.sum()) + np.float32(sum(us))
                agg = _weighted_sum([(w[i] / total, new[i]) for i in new
                                     if w[i] > 0], num)
                if ripe:
                    agg = jax.tree.map(jnp.add, agg, _weighted_sum(
                        [(np.float32(u) / total, b[3])
                         for u, b in zip(us, ripe)], num))
                params[lvl] = agg
            elif ripe:
                anchor = float(sum(n_eff[p] for p in members))
                total = anchor + float(sum(us))
                wa, ws = ((anchor / total, [u / total for u in us])
                          if total > 0 else (1.0, [0.0] * len(us)))
                params[lvl] = jax.tree.map(
                    lambda p, q: wa * p + q, params[lvl],
                    _weighted_sum(list(zip(ws, (b[3] for b in ripe))), num))
            for i, pid in enumerate(members):
                if dec[pid] == "banked":
                    bank[lvl].append((pid, r, n_eff[pid], new[i]))
            contrib = [mem_loss[i] for i in new if w[i] > 0]
            loss_r[lvl] = float(np.mean(contrib)) if contrib else float("nan")
        losses.append(loss_r)
        decisions.append(dec_r)
        after.append({l: jax.tree.map(np.asarray, p)
                      for l, p in params.items()})
    return losses, decisions, after


def _class_rows(y: np.ndarray, cls: np.ndarray, inst: np.ndarray):
    """Shard rows of the ``inst``-th sample of each slot's class."""
    rows = np.empty_like(inst)
    for slot, c in enumerate(cls):
        rows[:, slot] = np.flatnonzero(y == c)[inst[:, slot]]
    return rows


def _weighted_sum(terms, num):
    ws = jnp.asarray([float(w) for w, _ in terms], num.dtype)
    stack = _stack([p for _, p in terms])
    return jax.tree.map(
        lambda x: jnp.tensordot(ws, x, axes=(0, 0),
                                precision=num.precision).astype(num.dtype),
        stack)
