"""Driver for training: one ``build_train`` step object, driven from the seed.

Traffic keys: ``seq_len``, ``batch``, ``mesh``, ``comm_mode``, ``optimizer``
(the recipe the reference follows; see ``bench/references/train.py``) and
``trace_seconds``.  Set-up makes the state from the seed, then drives the
compiled step through its first three steps with the window's own feed,
reading what the check compares before step four overwrites it: each
step's loss, the first gradient (from AdamW's first moment after one step,
m = (1 - b1) g) and the parameters' change after three.  The window goes on
from step four with the same object.  Batches are made on the host, one a
step, by a copy of the program's synthetic token generator.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import program, seeds, weights
from bench.harness import Check, Measured
from bench.references import train as ref

#: limits on the three compared numbers, each between the largest reading
#: of sound runs and the smallest of the control or a fault (PERF.md)
MAX_LOSS_GAP = 0.003       # |loss - reference loss|, worst of steps 1-3
MAX_GRAD_NORM_GAP = 0.0006  # worst leaf, relative (see ``leaf_gap``)
MAX_CHANGE_NORM_GAP = 0.005
#: a leaf whose reference gradient is under this share of the median leaf's
#: moves by round-off alone under Adam; its change is not compared
STILL_LEAF = 1e-3
CHECKED_STEPS = 3


def batch(seed: int, step: int, B: int, S: int, vocab: int) -> dict:
    """Rows of ``S + 1`` ids: uniform starts plus a running sum of small
    drifts, modulo the vocabulary (the program's ``SyntheticTokenPipeline``
    generator, on the benchmark's own seed); tokens and next-token labels."""
    rng = seeds.numpy_rng(seed, f"train.batch.{step}")
    base = rng.integers(0, vocab, (B, S + 1))
    drift = rng.integers(0, 17, (B, S + 1))
    toks = (base + np.cumsum(drift, axis=1)) % vocab
    return {"tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32)}


def leaf_norms(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                                for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in zip(flat, norms)}


def diff_norms(a, b) -> dict:
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree.leaves(b)
    norms = jax.jit(lambda xs, ys: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32)))) for x, y in zip(xs, ys)])(
        [x for _, x in fa], fb)
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in zip(fa, norms)}


def leaf_gap(got: dict, want: dict, leaves=None) -> float:
    """Worst leaf's |norm - reference norm|, over the larger of that leaf's
    reference norm and the median leaf's."""
    leaves = list(want) if leaves is None else leaves
    med = float(np.median([want[k] for k in want]))
    return max(abs(got[k] - want[k]) / max(want[k], med, 1e-30) for k in leaves)


class Driver:
    def __init__(self, cell, *, seed: int, devices, seconds: float):
        self.cell, self.seed, self.devices = cell, seed, devices
        self.t, self.c = cell.traffic, cell.config
        self.B, self.S = int(self.t["batch"]), int(self.t["seq_len"])
        self.V = int(self.c["vocab_size"])

    def build(self, cfg, mesh):
        """The program's training step object (``build_train``)."""
        from repro.configs.base import ShapeConfig
        from repro.launch.steps import TrainSettings, build_train

        o = self.t["optimizer"]
        settings = TrainSettings(
            comm_mode=self.t["comm_mode"], base_lr=o["base_lr"],
            warmup_steps=o["warmup_steps"], total_steps=o["total_steps"],
            clip_norm=o["clip_norm"])
        return build_train(cfg, mesh, ShapeConfig("bench", self.S, self.B,
                                                  "train"), settings)

    def make_params(self, sharding):
        """The weights at step 0, made again from the seed."""
        return weights.make(self.pshapes, self.key, self.V, sharding)

    def setup(self) -> None:
        self.cfg = program.model_config(self.c)
        self.art = self.build(self.cfg, program.mesh(self.t["mesh"]))
        self.key = seeds.jax_key(self.seed, "weights")
        shapes = self.art["state_shape"]
        self.pshapes = shapes["params"]

        def make_state(k):
            opt = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                               shapes["opt"])
            return {"params": weights.build(shapes["params"], k, self.V),
                    "opt": opt}

        self.state = jax.jit(make_state,
                             out_shardings=self.art["state_sharding"])(self.key)
        self.step_no = 0
        self.losses = []
        b1 = self.t["optimizer"]["b1"]
        for i in range(CHECKED_STEPS):
            out = self._step()
            self.losses.append(float(out["loss"]))
            if i == 0:
                self.grad_norms = {k: v / (1 - b1) for k, v in
                                   leaf_norms(self.state["opt"]["m"]).items()}
        p0 = self.make_params(self.art["state_sharding"]["params"])
        self.change_norms = diff_norms(self.state["params"], p0)
        del p0

    def _step(self):
        b = batch(self.seed, self.step_no, self.B, self.S, self.V)
        self.state, out = self.art["step"](self.state, b)
        self.step_no += 1
        return out

    def measure(self, win) -> Measured:
        prev = None

        def sync():
            if prev is not None:
                prev.block_until_ready()

        n = 0
        win.start()
        while win.open:
            win.tick(sync)
            with jax.profiler.TraceAnnotation("bench.step"):
                loss = self._step()["loss"]
            if prev is not None:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    prev.block_until_ready()
            prev, n = loss, n + 1
        prev.block_until_ready()
        win.stop()
        return Measured(
            metrics={"train_tokens_per_s": n * self.B * self.S / win.elapsed},
            attempted=n, failed=0,
            stats={"traced": win.traced, "batch": self.B, "seq_len": self.S})

    def release(self) -> None:
        del self.state, self.art

    def reference(self, quantize: bool = False):
        """The reference's three steps from the same weights and batches:
        losses, first-gradient leaf norms, change leaf norms."""
        batches = []
        for i in range(CHECKED_STEPS):
            b = batch(self.seed, i, self.B, self.S, self.V)
            batches.append((jnp.asarray(b["tokens"]), jnp.asarray(b["labels"])))
        losses, g1, p3 = ref.train(self.make_params(None), batches, self.c,
                                   self.t["optimizer"], quantize)
        g1 = {jax.tree_util.keystr(p): float(x)
              for p, x in jax.tree_util.tree_flatten_with_path(g1)[0]}
        return losses, g1, diff_norms(p3, self.make_params(None))

    def compare(self, losses, grads, changes, ref_out) -> list[Check]:
        r_loss, r_grads, r_changes = ref_out
        med = float(np.median(list(r_grads.values())))
        moving = [k for k in r_grads if r_grads[k] >= STILL_LEAF * med]
        return [
            Check("loss_gap", max(abs(a - b) for a, b in zip(losses, r_loss)),
                  MAX_LOSS_GAP),
            Check("grad_norm_gap", leaf_gap(grads, r_grads), MAX_GRAD_NORM_GAP),
            Check("change_norm_gap", leaf_gap(changes, r_changes, moving),
                  MAX_CHANGE_NORM_GAP),
        ]

    def check(self) -> list[Check]:
        self.ref_out = self.reference()
        return self.compare(self.losses, self.grad_norms, self.change_norms,
                            self.ref_out)


def control(drv) -> dict:
    """The control's readings: the reference in float8 in the program's
    place, compared with the float32 reference as the program is."""
    low = drv.reference(quantize=True)
    return {c.name: c.value for c in drv.compare(*low, drv.ref_out)}
