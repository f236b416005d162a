"""The comparison that decides ``correct``.

The program's first dispatch blocks are its first steps: the rounds they
cover are replayed by the plain reference of the configuration's family
(``bench/references/<family>.py``), and these numbers are worked out; a
cell compares those that its ``bench/limits/<cell>.json`` gives a limit:

* ``loss_gap``: over every checked round and cluster level, the relative
  gap between the program's mean member loss and the reference's;
* ``grad_gap``: the first step's update (the planes after the first block
  less the initial weights), by the worst leaf: the gap between the
  program's norm of that leaf and the reference's, over the reference's
  norm of the leaf or of the median leaf, whichever is larger;
* ``change_gap``: the same for the change after the checked blocks, as
  the next block starts from it;
* ``grad_diff``, ``change_diff``: the same two updates by the worst leaf,
  the norm of the difference between the program's update and the
  reference's over the same denominator.  A gap of norms moves with
  rounding only along the update, so it cannot tell a lower precision
  from the program; the norm of the difference moves with all of it;
* ``mar_mismatch``: members whose MAR decision (active, banked, dropped,
  offline, ...) differs from the reference's, over all rounds; exact.

Leaves whose reference update is under a thousandth of the median leaf's
are left out of the leaf numbers (their update is round-off alone).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np
from jax import lax

TINY_LEAF = 1e-3


@dataclass(frozen=True)
class Numerics:
    """The arithmetic of a replay: the reference's, the control's, or a
    planted fault's."""
    dtype: object = jnp.float32
    precision: object = lax.Precision.HIGHEST
    batch_keep: float = 1.0        # < 1: the fault that drops part of a batch
    lr_scale: float = 1.0          # 0: the fault whose step changes nothing


REFERENCE = Numerics()
# The configuration stores float32 and runs its products at default
# precision; the control is the precision below: bfloat16 storage.
CONTROL = Numerics(dtype=jnp.bfloat16, precision=lax.Precision.DEFAULT)
FAULT_HALF_BATCH = Numerics(batch_keep=0.5)
FAULT_STUCK = Numerics(lr_scale=0.0)


def decisions_of(stats) -> dict:
    """pid -> decision, from one cluster's round record of the program."""
    out = {}
    for kind in ("active", "banked", "dropped", "offline", "unselected"):
        for pid in getattr(stats, kind):
            out[pid] = kind
    for pid in stats.masked:
        out[pid] = "masked"
    return out


def _leaves(tree) -> dict:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): np.asarray(v, np.float64)
            for k, v in flat}


def leaf_gaps(prog: dict, ref: dict, init: dict, diff: bool = False) -> dict:
    """{level: {leaf: relative gap}}: per leaf, the gap between the
    program's and the reference's norm of the change from ``init`` (with
    ``diff``, the norm of the difference of the two changes), over the
    reference's norm of that leaf or of the median leaf, whichever is
    larger.  A level or leaf the program lacks reads inf."""
    out = {}
    for lvl, r_tree in ref.items():
        r, i = _leaves(r_tree), _leaves(init[lvl])
        p = _leaves(prog[lvl]) if lvl in prog else {}
        dr = {k: np.linalg.norm(r[k] - i[k]) for k in r}
        med = float(np.median(list(dr.values())))
        gaps = {}
        for k in r:
            if dr[k] < TINY_LEAF * med:
                continue
            if k not in p:
                gaps[k] = math.inf
                continue
            if diff:
                gap = np.linalg.norm(p[k] - r[k]) / max(dr[k], med)
            else:
                gap = (abs(np.linalg.norm(p[k] - i[k]) - dr[k])
                       / max(dr[k], med))
            gaps[k] = float(gap) if np.isfinite(gap) else math.inf
        out[lvl] = gaps
    return out


def worst_leaf(gaps: dict) -> float:
    return max((g for by_leaf in gaps.values() for g in by_leaf.values()),
               default=0.0)


def loss_gaps(prog_losses: list, ref_losses: list) -> list:
    """Per round, {level: relative gap of the mean member loss}."""
    out = []
    for r, ref in enumerate(ref_losses):
        gaps = {}
        for lvl, lr in ref.items():
            if np.isfinite(lr):
                gap = abs(prog_losses[r].get(lvl, math.nan) - lr) / abs(lr)
                gaps[lvl] = float(gap) if np.isfinite(gap) else math.inf
        out.append(gaps)
    return out


def numbers(prog: dict, ref_losses: list, ref_dec: list, ref_params: list,
            init: dict) -> dict:
    """The compared numbers.  ``prog``: per round ``losses``
    {level: loss} and ``decisions`` {level: {pid: decision}}, and
    ``params`` {rounds completed: {level: params}} at block ends."""
    gaps = loss_gaps(prog["losses"], ref_losses)
    ends = sorted(prog["params"])
    mismatch = 0
    for r in range(len(ref_losses)):
        for lvl, dr in ref_dec[r].items():
            dp = prog["decisions"][r].get(lvl, {})
            mismatch += sum(dp.get(p) != dr.get(p) for p in set(dp) | set(dr))
    out = {"loss_gap": max((g for by_level in gaps
                            for g in by_level.values()), default=0.0)}
    for name, r in (("grad", ends[0]), ("change", ends[-1])):
        for kind, diff in (("gap", False), ("diff", True)):
            out[f"{name}_{kind}"] = worst_leaf(leaf_gaps(
                prog["params"][r], ref_params[r - 1], init, diff))
    out["mar_mismatch"] = float(mismatch)
    return out


def replay(reference, model: dict, fed: dict, table, shards: list,
           seed: int, layouts: list, num: Numerics) -> dict:
    """The family's reference (or, with other numerics, the control or a
    fault) over ``layouts``, in the form ``numbers`` compares."""
    losses, dec, params = reference.run(model, fed, table, shards, seed,
                                        layouts, num)
    return {"losses": losses, "decisions": dec, "params": params}


def initial(reference, model: dict, levels, seed: int) -> dict:
    return {lvl: reference.init_params(model, lvl, seed) for lvl in levels}


def compare(reference, prog: dict, model: dict, fed: dict, table,
            shards: list, seed: int, layouts: list) -> dict:
    """The program's first blocks against the float32 reference."""
    ref = replay(reference, model, fed, table, shards, seed, layouts,
                 REFERENCE)
    init = initial(reference, model, ref["params"][0].keys(), seed)
    return numbers(prog, ref["losses"], ref["decisions"], ref["params"],
                   init)


def as_program(out: dict, ends: list) -> dict:
    """A replay's outputs in the program's form, kept at ``ends`` (rounds
    completed at each block end): the control put in the program's place."""
    return {"losses": out["losses"], "decisions": out["decisions"],
            "params": {r: out["params"][r - 1] for r in ends}}
