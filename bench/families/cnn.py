"""The system under test for the CNN family: the program's ``FedRAC``
engine of a cell, set up through the program's public constructors as
``repro.launch.sim_run.build`` sets it up.

A family other than the CNN brings its own ``bench/families/<family>.py``
with ``build_engine``, found by the configuration's ``family``.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def build_engine(config: dict, fed: dict, seed: int, shards: list,
                 rounds_per_dispatch: int):
    """The cell's ``FedRAC`` engine, set up (clustering, compaction,
    Procedure 2).  ``fed`` is the configuration's federation settings with
    the traffic mix's overrides."""
    import jax
    from repro.core import server as srv
    from repro.core.families import cnn_family
    from repro.core.resources import participants_from_matrix

    m = config["model"]
    parts = participants_from_matrix(
        np.asarray(config["participants"]["table_iii"], np.float64),
        n_data=[len(s["y"]) for s in shards])
    fam = cnn_family(classes=m["classes"], in_channels=m["in_channels"],
                     alpha=m["alpha"], base_width=m["base_width"],
                     input_hw=m["input_hw"])
    # FLConfig.seed is a constant of every compiled block program (the
    # sampler's stream), so a new value recompiles them all; it stays the
    # configuration's ``program_seed`` and the run's seed keys the weights.
    init = fam.init
    fam = dataclasses.replace(fam, init=lambda key, level: init(
        jax.random.PRNGKey(seed + level), level))
    cfg = srv.FLConfig(
        alpha=m["alpha"], kd_T=fed["kd_T"], kd_alpha=fed["kd_alpha"],
        E=fed["epochs"], local_batch=fed["local_batch"],
        steps_per_round=fed["steps_per_round"], lr=fed["lr"],
        lam=tuple(fed["lam"]), kappa=fed["kappa"],
        compact_to=fed["compact_to"], seed=fed["program_seed"],
        class_balanced=fed["class_balanced"],
        aggregation="buffered" if fed["mar_policy"] == "buffer" else "sync",
        staleness_discount=fed["staleness_discount"],
        rounds_per_dispatch=rounds_per_dispatch)
    return srv.FedRAC(parts, shards, fam, cfg, classes=m["classes"]).setup()
