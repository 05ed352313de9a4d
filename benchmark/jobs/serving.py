"""What the two serving jobs share: the engine as a deployment would hold
it, warmed, and checked against the plain reference."""
from __future__ import annotations

from typing import Dict

import numpy as np

from .. import say, system
from . import checks


class Served:
    def __init__(self, ctx):
        from hydragnn_tpu.config import build_model_config
        from hydragnn_tpu.models.create import create_model
        cell = ctx.cell
        self.doc = doc = (system.apply_tiny(cell.config_doc) if ctx.tiny
                          else cell.config_doc)
        self.pools = pool, valset, testset = system.load_pools(doc)
        # the structures served, and what the engine sizes its buckets
        # from: the head of the pool, the same for every seed
        self.structures = pool[:int(ctx.param("structures"))]
        self.config = system.complete_config(
            doc, self.pools,
            doc["hydragnn"]["NeuralNetwork"]["Training"]["batch_size"],
            serving=ctx.param("serving"))
        self.arch = self.config["NeuralNetwork"]["Architecture"]
        self.mcfg = build_model_config(self.config)
        self.model = create_model(self.mcfg)
        say(f"pools loaded: {len(pool)} + {len(valset)} + {len(testset)} "
            "structures")
        self.check = system.check_structures(testset)
        self._reference = None
        self.variables = system.init_variables(self.model, self.check,
                                               ctx.seed)
        self.engine = system.make_engine(self.config, self.model, self.mcfg,
                                         self.variables, self.structures,
                                         self.pools)

    def reference(self):
        """(E, F, structure dict) of the plain reference on the check
        structures, with the served weights; computed once."""
        if self._reference is None:
            self._reference = system.reference_energy_forces(
                self.doc, self.config, self.variables, self.check,
                train=False)
        return self._reference

    def warm_up(self) -> None:
        """Compile every bucket, run each once (the first execution of a
        program is not a steady one, and the peak of device memory is the
        largest bucket's whether or not the window fills it), and answer
        the check structures once, as run."""
        engine = self.engine
        programs = engine.warmup()
        small = min(self.structures, key=lambda s: s.num_nodes)
        for bucket in engine.buckets:
            engine.forward_single(small, bucket=bucket)
        say(f"layout: neighbor_format={engine.neighbor_k is not None} K="
            f"{engine.neighbor_k}; {programs} bucket programs, largest "
            f"{engine.buckets[-1].n_node} nodes x "
            f"{engine.buckets[-1].n_edge} edges for "
            f"{engine.buckets[-1].cap_graphs} structures")
        self.as_run = engine.predict(self.check, timeout=600)

    def highest_engine(self, config=None, model=None, mcfg=None):
        """A second engine of the one bucket the check structures fall in,
        compiled at highest matmul precision (`jobs/checks.py`). Its
        programs take the weights as arguments: `swap_variables` gives it
        another seed's."""
        import jax
        from hydragnn_tpu.serving.engine import select_bucket
        bucket = select_bucket(
            self.engine.buckets, len(self.check),
            sum(s.num_nodes for s in self.check),
            sum(s.num_edges for s in self.check))
        with jax.default_matmul_precision("highest"):
            exact = system.make_engine(
                config or self.config, model or self.model,
                mcfg or self.mcfg, self.variables, self.structures,
                self.pools, buckets=[bucket])
            exact.warmup()
        return exact

    def compare(self, out: checks.Compared, label: str, tol: Dict, got
                ) -> None:
        """Served (energy, forces) answers on the check structures against
        the plain reference."""
        ref_e, ref_f, _ = self.reference()
        out.arrays(label, np.array([r[0][0] for r in got]),
                   np.concatenate([r[1] for r in got]), ref_e, ref_f, tol,
                   f"{len(self.check)} check structures, one bucket")

    def judge(self) -> checks.Compared:
        """After the window: the engine's energies and forces on the check
        structures against the plain reference, as they were served and
        from the same forward at highest matmul precision."""
        exact = self.highest_engine()
        try:
            at_highest = exact.predict(self.check, timeout=600)
        finally:
            exact.shutdown()
        out = checks.Compared(say)
        self.compare(out, "engine_at_highest", checks.HIGHEST_TOL,
                     at_highest)
        self.compare(out, "engine_as_run", checks.AS_RUN_TOL, self.as_run)
        return out
