"""The one general traffic generator: it turns a configuration file and a
traffic file into calls of the system under test, and checks what those
calls returned against the plain reference (``bench/reference.py``).

A configuration file is a deployment: the ``SimConfig`` fields of its
area, nodes, radio and contact path (``DEPLOYMENT_KEYS``) and its protocol
defaults (``PROTOCOL_KEYS``). A traffic file is a study run on it:

* ``points``: the scenarios of one call, each a dict of the protocol
  fields it sets (``lam``, ``Lam``, ``T_T``, ``T_M``, ``L``, ...) over the
  configuration's defaults;
* ``seeds_per_call``: fresh seeds per scenario and call;
* ``run``: the study's ``n_slots``, ``sample_every`` and ``warmup_frac``;
* ``reduce``: the on-device reduction (``"mean"``);
* ``learn`` (optional): ``LearnConfig`` fields that turn the learning
  layer on;
* ``metric``, ``check_runs``, ``limits``: the rate it reports, the runs
  its check recomputes, and the limit of each number compared.

Each call is one ``repro.sim.sweep.run`` over every point times the
call's seeds; its work is its slot-runs. Every seed a call uses is drawn
from the run's ``--seed`` and the call's index, so the same ``--seed``
gives the same calls; the warm-up call draws from an index no timed call
uses. The program receives only the ``FGParams`` grid, the ``SimConfig``
and the seeds.
"""

from __future__ import annotations

import math

import numpy as np

from bench import reference as ref

#: ``SimConfig`` fields a configuration file sets.
DEPLOYMENT_KEYS = ("n_nodes", "area_side", "rz_radius", "r_tx", "speed",
                   "dir_change_rate", "dt", "k_obs", "q_train", "q_merge",
                   "mobility", "contact_backend", "overflow_mode")
#: ``SimConfig`` fields a traffic file's ``run`` sets.
RUN_KEYS = ("n_slots", "sample_every", "warmup_frac")
#: ``FGParams`` fields of the protocol (defaults in the configuration, a
#: traffic point may set any of them).
PROTOCOL_KEYS = ("lam", "Lam", "M", "W", "T_T", "T_M", "t0", "L", "C", "k",
                 "tau_l")
#: Index of the warm-up call's seed draw (timed calls use 0, 1, ...).
WARMUP_CALL = 2**32 - 1
#: Index of the draw of the runs the check compares.
CHECK_DRAW = 2**32 - 2
#: Floor of the scale a gap is measured against.
TINY = 1e-12


def call_seeds(seed: int, call: int, n: int) -> list[int]:
    rng = np.random.default_rng([seed % 2**64, call])
    return [int(s) for s in rng.integers(0, 2**31, size=n)]


def study(cfg: dict, traffic: dict) -> dict:
    """The configuration with the traffic's run fields: every ``SimConfig``
    field of the cell."""
    return {**cfg, **traffic["run"]}


def protocol(cfg: dict, point: dict) -> dict:
    """The protocol fields of one scenario: the point over the defaults."""
    unknown = set(point) - set(PROTOCOL_KEYS)
    if unknown:
        raise KeyError(f"a traffic point sets unknown fields {unknown}")
    return {k: point.get(k, cfg.get(k)) for k in PROTOCOL_KEYS}


def fg_params(cfg: dict, point: dict):
    """The protocol's ``FGParams`` at the deployment's density: N and alpha
    describe the RZ as the paper's scenario does (the engine itself reads
    only the timing and rate fields)."""
    from repro.core.meanfield import FGParams

    q = protocol(cfg, point)
    density = cfg["n_nodes"] / cfg["area_side"] ** 2
    r = cfg["rz_radius"]
    return FGParams(
        N=density * math.pi * r * r, alpha=2.0 * density * cfg["speed"] * r,
        lam=q["lam"], Lam=float(q["Lam"]), M=q["M"], W=q["W"], T_T=q["T_T"],
        T_M=q["T_M"], t0=q["t0"], L=q["L"], C=q["C"], k=q["k"],
        tau_l=q["tau_l"])


def ref_shape(cfg: dict, points) -> ref.Shape:
    """The reference's static sizes; ``cfg`` is a :func:`study`. ``M`` and
    ``Lam`` are static there, so every point must share them."""
    qs = [protocol(cfg, pt) for pt in points]
    static = {(q["M"], int(q["Lam"])) for q in qs}
    if len(static) != 1:
        raise ValueError(f"the points of one call differ in (M, Lam): "
                         f"{sorted(static)}")
    M, Lam = static.pop()
    return ref.Shape(
        n_nodes=cfg["n_nodes"], area_side=cfg["area_side"],
        rz_radius=cfg["rz_radius"], r_tx=cfg["r_tx"], speed=cfg["speed"],
        dir_change_rate=cfg["dir_change_rate"], dt=cfg["dt"],
        n_slots=cfg["n_slots"], k_obs=cfg["k_obs"], q_train=cfg["q_train"],
        q_merge=cfg["q_merge"], M=M, Lam=Lam)


def ref_params(cfg: dict, points) -> dict:
    """The dynamic protocol parameters per run, as float32 (the program's
    own dtype for them)."""
    qs = [protocol(cfg, pt) for pt in points]
    p = {k: np.asarray([q[k] for q in qs], np.float32)
         for k in ("t0", "T_T", "T_M", "tau_l", "lam")}
    p["T_L"] = np.asarray([2.0 * q["L"] / q["C"] for q in qs], np.float32)
    return p


def ref_learn(learn: dict | None):
    if learn is None:
        return None
    if learn.get("model", "logreg") != "logreg" or \
            learn.get("merge_policy", "obs_count") != "obs_count":
        raise ValueError("the reference covers logreg with obs_count merges")
    return ref.Learn(
        n_features=learn["n_features"], n_classes=learn["n_classes"],
        lr=learn["lr"], batch=learn["batch"], n_test=learn["n_test"],
        label_noise=learn["label_noise"], data_seed=learn["data_seed"])


def gap(prog, want) -> float:
    """Widest gap between the program's values and the reference's,
    relative to the largest reference magnitude (equal infinities, such as
    empty observation slots, are no gap)."""
    p = np.asarray(prog, np.float64).ravel()
    w = np.asarray(want, np.float64).ravel()
    same = (p == w) | (np.isnan(p) & np.isnan(w))
    if same.all():
        return 0.0
    fin = np.isfinite(w)
    scale = max(float(np.max(np.abs(w[fin]))) if fin.any() else 0.0, TINY)
    d = np.where(same, 0.0, np.abs(p - w))
    return float(np.max(np.where(np.isnan(d), np.inf, d)) / scale)


#: Statistics of a reduced sweep and the reference output each reduces.
PROTOCOL_STATS = {
    "availability": "availability", "availability_z": "availability",
    "busy_frac": "busy_frac", "stored": "stored", "stored_z": "stored",
    "model_holders": "model_holders", "n_in_rz": "n_in_rz",
    "n_in_rz_z": "n_in_rz",
}
LEARN_STATS = ("test_acc", "test_acc_holders", "learn_obs", "theta_var")


class Driver:
    """Calls of one cell (see the module doc)."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.sim import SimConfig

        if traffic["reduce"] != "mean":
            raise ValueError("the check covers reduce='mean' sweeps")
        self.cfg = study(cfg, traffic)
        self.traffic, self.seed = traffic, seed
        learn = traffic.get("learn")
        kw = {k: self.cfg[k] for k in DEPLOYMENT_KEYS + RUN_KEYS}
        if learn is not None:
            from repro.sim.learn import LearnConfig

            kw["learn"] = LearnConfig(**learn)
        self.sim_cfg = SimConfig(**kw)
        self.points = list(traffic["points"])
        self.params = [fg_params(self.cfg, pt) for pt in self.points]
        self.shape = ref_shape(self.cfg, self.points)
        self.n_seeds = traffic["seeds_per_call"]
        self.runs_per_call = len(self.points) * self.n_seeds
        self.work_per_call = float(self.cfg["n_slots"] * self.runs_per_call)
        self.done: list = []          # per timed call: (seeds, stats)

    # -- the timed path ------------------------------------------------
    def run_call(self, call: int):
        from repro.sim import sweep

        seeds = call_seeds(self.seed, call, self.n_seeds)
        out = sweep.run(self.params, self.sim_cfg, seeds,
                        reduce=self.traffic["reduce"])
        if out.failed_chunks:
            raise RuntimeError(f"sweep chunks failed: {out.failed_chunks}")
        return seeds, out.stats

    def call(self, call: int) -> float:
        """Run timed call ``call``, keep its outputs for the check, and
        return its work."""
        self.done.append(self.run_call(call))
        return self.work_per_call

    def warm(self):
        self.run_call(WARMUP_CALL)

    # -- the check -----------------------------------------------------
    def _sample(self, n_items: int, k: int) -> list[int]:
        rng = np.random.default_rng([self.seed % 2**64, CHECK_DRAW])
        return sorted(rng.choice(n_items, size=min(k, n_items),
                                 replace=False).tolist())

    def _reference(self, seeds, points, fdt=None):
        import jax
        import jax.numpy as jnp

        keys = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
        p = {k: jnp.asarray(v) for k, v in
             ref_params(self.cfg, points).items()}
        out = ref.run_many(keys, p, self.shape,
                           jnp.float32 if fdt is None else fdt,
                           ref_learn(self.traffic.get("learn")))
        return jax.tree_util.tree_map(np.asarray, out)

    def check(self, control: bool = False) -> dict:
        """Gaps between the outputs of a seeded sample of the window's runs
        and the reference's, each run recomputed and reduced as the sweep
        reduces (post-warm-up mean and standard deviation of the samples).
        With ``control``, the reference computed in bfloat16 takes the
        program's place (the comparison must fail it)."""
        import jax.numpy as jnp

        n_p, n_r = len(self.points), self.n_seeds
        picks = self._sample(len(self.done) * n_p * n_r,
                             self.traffic["check_runs"])
        idx = [(i // (n_p * n_r), i // n_r % n_p, i % n_r) for i in picks]
        seeds = [self.done[c][0][r] for c, _, r in idx]
        points = [self.points[p] for _, p, _ in idx]
        want = self._reference(seeds, points)
        got = (self._reference(seeds, points, jnp.bfloat16) if control
               else None)
        pts = ref.sample_points(self.cfg["n_slots"], self.cfg["sample_every"])
        s0 = min(int(len(pts) * self.cfg["warmup_frac"]), len(pts) - 1)

        def reduced(outs, key):
            v = outs[key][:, pts][:, s0:].astype(np.float64)
            return {"": v.mean(axis=1), "_std": v.std(axis=1)}

        def judged(key, rk, suffix):
            if got is not None:
                return reduced(got, rk)[suffix]
            return np.stack([self.done[c][1][key + suffix][p, r]
                             for c, p, r in idx])

        def gaps(stats):
            g = 0.0
            for key, rk in stats.items():
                for suffix, w in reduced(want, rk).items():
                    g = max(g, gap(judged(key, rk, suffix).reshape(w.shape),
                                   w))
            return g

        out = {"protocol_gap": gaps(PROTOCOL_STATS)}
        if self.traffic.get("learn") is not None:
            merges = (got["n_merges"][:, pts[-1]] if got is not None else
                      np.stack([self.done[c][1]["merge_stats"][p, r][0]
                                for c, p, r in idx]))
            out["learn_gap"] = max(gaps({k: k for k in LEARN_STATS}),
                                   gap(merges, want["n_merges"][:, pts[-1]]))
        return out


def make(cfg: dict, traffic: dict, seed: int) -> Driver:
    return Driver(cfg, traffic, seed)
