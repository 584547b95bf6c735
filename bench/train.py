"""The training driver: a cell whose job file says ``"driver":
"train"``.

The program under test is ``Trainer.create(..., dp=DPConfig(strategy=
<the job's>))`` on a ``("data",)`` mesh over the cell's chips, with
the benchmark's seeded weights (float32 master copies of the bfloat16
values) and AdamW as the job file states it.  Set-up builds that one
trainer, drives it through its first ``check_steps`` steps with the
same call and feed that the window uses, and keeps what the check
reads: each step's loss, the first gradient as the optimizer got it
(its first moment after step 1, over 1 - b1) and the parameters after
the last of those steps.  The window then goes on stepping the same
trainer; each step's rows are drawn from the seed and the step's
number, so every row differs.

``train_tok_s`` is the tokens of every step in the window over the
window, which closes when the first step to end after ``--seconds``
ends.

``correct`` compares the readings with the plain reference
(``bench/reference/train.py``) over the same steps: the largest gap of
a step's loss, relative to the reference's; and by the worst leaf (each
layer of a stacked weight apart), the gap between the program's and the
reference's norm of the first gradient, and of the change of the
parameters, each over the larger of the reference's norm of that leaf
and of the median leaf.  Leaves whose reference gradient lies under a
thousandth of the median leaf's move by round-off alone and are left
out of the change.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict

import numpy as np

from bench import model, work
from bench.reference import forward
from bench.reference import train as reference

TOKEN_KEY = 2 ** 30           # folded into the seed's key for the rows


def hyper(job: dict) -> dict:
    return {k: float(job[k]) for k in ("lr", "b1", "b2", "eps",
                                       "weight_decay")}


def tokens(cfg: dict, seed: int, step: int, rows: int, seq: int,
           sharding=None):
    """The rows of step ``step``: uniform token ids from the seed."""
    import jax

    key = jax.random.fold_in(
        jax.random.fold_in(model.root_key(seed), TOKEN_KEY), step)
    return jax.jit(lambda k: jax.random.randint(
        k, (rows, seq), 0, cfg["vocab_size"], "int32"),
        out_shardings=sharding)(key)


def named(tree) -> Dict[str, np.ndarray]:
    """The program's parameter tree by the reference's leaf names: the
    outer weights under ``outer/``, the stacked layers under
    ``layers/``."""
    import jax

    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = model._leaf_name(path)
        head = "decoder/blocks/layer0/"
        out[("layers/" + name[len(head):]) if name.startswith(head)
            else "outer/" + name] = x
    return out


def norms(arrays: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """As ``reference.leaf_norms``, on the host."""
    out = {}
    for k, x in arrays.items():
        x = np.asarray(x, np.float32)
        if k.startswith("layers"):
            out[k] = np.sqrt(np.sum(np.square(x.reshape(len(x), -1),
                                              dtype=np.float64), axis=1))
        else:
            out[k] = np.sqrt(np.sum(np.square(x, dtype=np.float64)))
    return out


def worst_leaf(got: Dict, want: Dict, keep=None) -> float:
    """The largest gap of a leaf's norm, over the larger of the
    reference's norm of that leaf and of the median leaf."""
    ref = np.concatenate([np.ravel(want[k]) for k in sorted(want)])
    med = float(np.median(ref))
    worst = 0.0
    for k in want:
        w, g = np.ravel(want[k]), np.ravel(got[k])
        ok = np.ones_like(w, bool) if keep is None else np.ravel(keep[k])
        if ok.any():
            gap = np.abs(g - w)[ok] / np.maximum(w[ok], med)
            worst = max(worst, float(gap.max()))
    return worst


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    """The numbers that decide ``correct``."""
    g = ref["grad_norms"]
    med = float(np.median(np.concatenate([np.ravel(x) for x in g.values()])))
    moved = {k: np.ravel(x) >= 1e-3 * med for k, x in g.items()}
    lw, lp = np.array(ref["losses"]), np.array(prog["losses"])
    return {"loss_gap": float(np.max(np.abs(lp - lw) / np.abs(lw))),
            "grad_gap": worst_leaf(prog["grad_norms"], g),
            "change_gap": worst_leaf(prog["change_norms"],
                                     ref["change_norms"], moved)}


class Job:
    """One training cell: the trainer, its first steps, the window."""

    def __init__(self, cfg: dict, job: dict, seed: int, seconds: float,
                 devices, log):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro import optim
        from repro.api import Trainer
        from repro.core import DPConfig
        from repro.core.train_state import host_params
        from repro.train.step import TrainConfig

        self.cfg, self.job, self.seed, self.seconds = cfg, job, seed, seconds
        self.devices, self.log = devices, log
        self.rows = int(job["rows_per_chip"]) * len(devices)
        self.seq = int(job["seq"])
        mesh = Mesh(np.array(devices), ("data",))
        self.row_sharding = NamedSharding(mesh, P("data"))
        hp = hyper(job)
        t0 = time.perf_counter()
        pcfg = dataclasses.replace(model.program_config(cfg),
                                   param_dtype="float32")
        w = model.program_weights(cfg, pcfg, seed)
        host = jax.tree_util.tree_map(
            lambda x: np.asarray(x).astype(np.float32), w)
        del w
        t1 = time.perf_counter()
        opt = optim.get_optimizer(job["optimizer"], hp["lr"],
                                  weight_decay=hp["weight_decay"],
                                  b1=hp["b1"], b2=hp["b2"], eps=hp["eps"])
        self.trainer = Trainer.create(
            model_cfg=pcfg, params=host, optimizer=opt,
            train_cfg=TrainConfig(optimizer=job["optimizer"], lr=hp["lr"],
                                  remat=bool(job["remat"])),
            dp=DPConfig(strategy=job["strategy"]), mesh=mesh, donate=True)
        p0 = named(host)
        del host
        t2 = time.perf_counter()
        losses = []
        for i in range(int(job["check_steps"])):
            met = self.trainer.step(self.batch(i))
            losses.append(float(met["loss"]))
            if i == 0:
                st = self.trainer.state
                m = host_params(dataclasses.replace(
                    st, params=st.opt_state["m"]["flat"]))
                grad = norms({k: v / (1 - hp["b1"])
                              for k, v in named(m).items()})
                del m
        p = named(host_params(self.trainer.state))
        change = norms({k: p[k] - p0[k] for k in p0})
        del p, p0
        self.prog = {"losses": losses, "grad_norms": grad,
                     "change_norms": change}
        self.step = len(losses)
        self.nxt = self.batch(self.step)
        log(f"setup: weights {t1 - t0:.3f} s, trainer {t2 - t1:.3f} s, "
            f"first {self.step} steps {time.perf_counter() - t2:.3f} s "
            f"(losses {losses}); {self.rows} rows of {self.seq} tokens "
            f"on {len(devices)} chips, {job['strategy']}")
        self.window = None

    def batch(self, step: int) -> dict:
        return {"tokens": tokens(self.cfg, self.seed, step, self.rows,
                                 self.seq, self.row_sharding)}

    # ------------------------------------------------------------------
    def run(self, annotate=None) -> None:
        span = annotate or (lambda name: contextlib.nullcontext())
        T = float(self.seconds)
        clock = time.perf_counter
        ends, losses = [], []
        t0 = clock()
        with span("window"):
            while not ends or ends[-1] < T:
                with span("step"):
                    met = self.trainer.step(self.nxt)
                    self.step += 1
                    self.nxt = self.batch(self.step)
                    losses.append(float(met["loss"]))   # waits for it
                ends.append(clock() - t0)
        self.window = {"steps": len(ends), "seconds": ends[-1]}
        d = np.diff([0.0] + ends)
        self.log(f"window: {len(ends)} steps in {ends[-1]:.3f} s, step "
                 f"seconds min {d.min():.4f} median {np.median(d):.4f} "
                 f"max {d.max():.4f}; losses first {losses[0]} last "
                 f"{losses[-1]}")

    def release(self):
        self.trainer = None
        self.nxt = None

    def end_to_end(self) -> Dict[str, float]:
        w = self.window
        return {"train_tok_s": w["steps"] * self.rows * self.seq
                / w["seconds"], "_attempted": w["steps"], "_failed": 0}

    def work(self, device_kind: str) -> dict:
        flops = work.train_step_flops(self.cfg, self.rows, self.seq)
        return {"train": {"flops": self.window["steps"] * flops,
                          "chips": len(self.devices)}}

    # ------------------------------------------------------------------
    def reference(self, **fault) -> dict:
        batches = [np.asarray(self.batch(i)["tokens"])
                   for i in range(len(self.prog["losses"]))]
        return reference.run(self.cfg, self.seed, batches, hyper(self.job),
                             self.devices, **fault)

    def check(self, limits: dict, control: bool = False) -> dict:
        from bench.harness import passed

        t0 = time.perf_counter()
        ref = self.reference()
        self.log(f"check: reference {time.perf_counter() - t0:.3f} s, "
                 f"losses {ref['losses']} (program "
                 f"{self.prog['losses']})")

        def checks(prog):
            return {k: {"value": v, "limit": limits[k]["limit"]}
                    for k, v in compare(prog, ref).items()}
        out = {"checks": checks(self.prog)}
        if control:
            n = len(self.devices)
            faults = {
                "control": {"transform": forward.fp8_weights},
                "half_batch": {"rows": slice(0, self.rows // 2)},
                "no_exchange": {"rows": slice(0, self.rows // n),
                                "scale": 1.0 / n}}
            read = {k: checks(self.reference(**f)) for k, f in faults.items()}
            out["control"] = {"checks": read.pop("control"), "faults": {
                k: dict(checks=c, correct=passed(c)) for k, c in read.items()}}
        return out
