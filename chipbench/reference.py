"""Plain reference of the first steps of an async HeLoCo training job.

Written from the published equations and the job's data files, with
nothing imported from the program under test: AdamW with global-norm
clipping and warm-up/cosine schedule, the pseudo-gradient, the
per-tensor int8 round trip with error feedback, the HeLoCo per-block
correction with the outer Nesterov step, the momentum look-ahead worker
start, the synthetic per-language corpus and the virtual-clock arrival
order. The model (its weights from the seed, by the key derivation the
configuration's initialisation states, and its loss) comes from the
configuration's architecture module, ``chipbench/arch/<kind>.py``.

``matmul`` names the precision of the model's matrix products
(``chipbench/precision.py``): "bf16" follows the configuration, "fp8" is
the lower precision that serves as the control. ``fault`` plants one of
the faults a training step can have: "half_batch" takes the loss over
the first half of each batch only, "state_unchanged" makes every commit
leave the server's state as it was, "delta_altered" doubles the first
tensor of every pseudo-gradient where it is made. Every other
computation runs in float32 at full matmul precision.
"""
from __future__ import annotations

import heapq
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LANGS = ("de", "en", "es", "fr", "it")


# ---------------------------------------------------------------------------
# Synthetic per-language corpus (same generator as the program's trainer)
# ---------------------------------------------------------------------------

def language_specs(vocab: int, n_langs: int, seed: int) -> List[dict]:
    rng = np.random.default_rng(seed)
    shared = max(8, vocab // 8)
    per = (vocab - shared) // n_langs
    out = []
    for i in range(n_langs):
        lo = shared + i * per
        out.append(dict(
            lang=LANGS[i % len(LANGS)] + ("" if i < len(LANGS) else str(i)),
            lo=lo, hi=lo + per, shared_lo=0, shared_hi=shared,
            a=int(rng.integers(3, 17)) * 2 + 1, b=int(rng.integers(1, per)),
            noise=0.12 + 0.03 * i, share_p=0.15))
    return out


def sample_tokens(spec: dict, batch: int, seq: int,
                  rng: np.random.Generator) -> np.ndarray:
    """(batch, seq + 1) ids of an affine bigram process with Zipf
    innovations over the language's own range, plus shared ids."""
    width = spec["hi"] - spec["lo"]
    out = np.empty((batch, seq + 1), np.int64)
    state = rng.integers(0, width, size=batch)
    zipf = np.minimum(rng.zipf(1.5, size=(batch, seq + 1)), width) - 1
    noise = rng.random((batch, seq + 1)) < spec["noise"]
    share = rng.random((batch, seq + 1)) < spec["share_p"]
    shared = rng.integers(spec["shared_lo"], spec["shared_hi"],
                          size=(batch, seq + 1))
    for t in range(seq + 1):
        state = (spec["a"] * state + spec["b"]) % width
        state = np.where(noise[:, t], (state + zipf[:, t]) % width, state)
        out[:, t] = np.where(share[:, t], shared[:, t], spec["lo"] + state)
    return out.astype(np.int32)


def worker_batch(specs, lang: int, batch: int, seq: int, worker_seed: int,
                 step: int) -> Dict[str, np.ndarray]:
    """Batch ``step`` of a worker that reads one language."""
    rng = np.random.default_rng(
        (worker_seed * 1_000_003 + lang * 101 + step) % (2 ** 63))
    toks = sample_tokens(specs[lang], batch, seq, rng)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def eval_set(specs, batch: int, seq: int, seed: int) -> List[dict]:
    rng = np.random.default_rng(seed)
    out = []
    for spec in specs:
        toks = sample_tokens(spec, batch, seq, rng)
        out.append({"tokens": toks[:, :-1], "labels": toks[:, 1:]})
    return out


# ---------------------------------------------------------------------------
# Arrival order on the virtual clock
# ---------------------------------------------------------------------------

def scheduled_steps(paces: Sequence[float], n_workers: int, h: int,
                    commit_batch: int, n_steps: int
                    ) -> List[List[Tuple[int, int, int]]]:
    """The first ``n_steps`` commits as groups of (wid, s_i, staleness).
    Worker w returns every h * pace_w virtual seconds; returns at one
    tick commit together, up to ``commit_batch`` at a time, in dispatch
    order; a worker is dispatched again after the commit that took it."""
    pace = [paces[w % len(paces)] for w in range(n_workers)]
    heap = [(h * pace[w], w, w) for w in range(n_workers)]
    heapq.heapify(heap)
    s_i = {w: 0 for w in range(n_workers)}
    seq, t, steps = n_workers, 0, []
    while len(steps) < n_steps:
        tick = heap[0][0]
        group = []
        while heap and heap[0][0] == tick and len(group) < commit_batch:
            group.append(heapq.heappop(heap))
        steps.append([(w, s_i[w], t + j - s_i[w])
                      for j, (_, _, w) in enumerate(group)])
        t += len(group)
        for tick_w, _, w in group:
            s_i[w] = t
            heapq.heappush(heap, (tick_w + h * pace[w], seq, w))
            seq += 1
    return steps


# ---------------------------------------------------------------------------
# Optimisers
# ---------------------------------------------------------------------------

def adamw_step(params, grads, opt, inner: dict):
    """One AdamW step: global-norm clip, linear warm-up then cosine decay
    to a tenth, bias-corrected moments, decoupled weight decay."""
    mu, nu, count = opt
    if inner["grad_clip"] > 0:
        gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, inner["grad_clip"] / jnp.maximum(gn, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
    count = count + 1
    step = count.astype(jnp.float32)
    base, warm, total = inner["lr"], inner["warmup_steps"], \
        inner["total_steps"]
    if inner["schedule"] == "cosine":
        prog = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
        lr = jnp.where(step < warm, base * step / max(warm, 1),
                       0.1 * base + 0.9 * base * 0.5
                       * (1.0 + jnp.cos(jnp.pi * prog)))
    else:
        lr = jnp.asarray(base, jnp.float32)
    b1, b2 = inner["b1"], inner["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    bc1, bc2 = 1.0 - b1 ** step, 1.0 - b2 ** step
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2)
                                               + inner["eps"])
                                  + inner["weight_decay"] * p),
        params, mu, nu)
    return params, (mu, nu, count)


def heloco_correct(u, v, h: dict):
    """Correct one tensor's pseudo-gradient u against its momentum v:
    keep it when cos(u, v) >= c_ok; damp the anti-momentum part when the
    cosine is negative; otherwise rotate it towards v at equal norm."""
    nu, nv = jnp.linalg.norm(u), jnp.linalg.norm(v)
    u_hat = u / jnp.maximum(nu, h["eps"])
    v_hat = v / jnp.maximum(nv, h["eps"])
    c = jnp.sum(u_hat * v_hat)
    conf = nu / (nu + h["kappa"] * nv + h["eps"])
    beta = jnp.minimum(h["k_s"] * (-c) * conf, h["beta_max"])
    anti = u - beta * c * nu * v_hat
    lam = jnp.minimum(h["k_d"] * (1.0 - c) * conf, 1.0)
    tilt = (1.0 - lam) * u_hat + lam * v_hat
    weak = nu * tilt / jnp.maximum(jnp.linalg.norm(tilt), h["eps"])
    out = jnp.where(c >= h["c_ok"], u, jnp.where(c < 0.0, anti, weak))
    return jnp.where((nu < h["eps"]) | (nv < h["eps"]), u, out)


def int8_roundtrip(x):
    """Per-tensor symmetric int8 with round-half-to-even."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


# ---------------------------------------------------------------------------
# The job's first steps
# ---------------------------------------------------------------------------

def leaf_names(tree) -> List[str]:
    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in jax.tree.leaves(tree)])


class Reference:
    """Follows a job from the seed through its first commits, with the
    model of ``arch``, an architecture module (``chipbench/arch/``), at
    the configuration ``model_cfg``."""

    FAULTS = ("", "half_batch", "state_unchanged", "delta_altered")
    #: fields of the job the reference follows only at these values
    FIXED = {"non_iid": True, "mixture_alpha": None, "dylu": False,
             "shard_assignment": "fixed", "topology": "hub",
             "batch_rampup": None, "grad_accum": 1}

    def __init__(self, arch, model_cfg: dict, job: dict, seed: int, *,
                 matmul: str = "bf16", fault: str = ""):
        if fault not in self.FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        o = job["outer"]
        if (o["method"], o["weight_factor"], job["inner"]["optimizer"]) != (
                "heloco", "base", "adamw"):
            raise ValueError("the reference follows HeLoCo with base "
                             "weighting and AdamW inner steps only")
        for key, value in self.FIXED.items():
            if job.get(key, value) != value:
                raise ValueError(f"the reference follows {key}={value!r} "
                                 f"only, not {job[key]!r}")
        self.arch, self.cfg, self.job, self.seed, self.fault = \
            arch, model_cfg, job, seed, fault
        self.n = job["n_workers"]
        self.specs = language_specs(model_cfg["vocab_size"],
                                    max(self.n, 2), seed)
        self.eval_set = eval_set(self.specs, job["eval_batch"],
                                 job["seq_len"], seed + 4242)
        loss = arch.make_loss(model_cfg, matmul, fault == "half_batch")
        eval_loss = arch.make_loss(model_cfg, matmul, False)
        inner = job["inner"]

        def train_step(params, opt, tokens, labels):
            grads = jax.grad(loss)(params, tokens, labels)
            return adamw_step(params, grads, opt, inner)

        self._step = jax.jit(train_step)
        self._grad = jax.jit(jax.grad(loss))
        self._eval = jax.jit(eval_loss)
        self._commit = jax.jit(self._commit_fn)

    def _commit_fn(self, params, mom, delta, rho):
        o = self.job["outer"]
        g = jax.tree.map(lambda u, v: heloco_correct(u, v, o["heloco"]),
                         delta, mom)
        mom = jax.tree.map(lambda m, gi: o["momentum"] * m
                           + (1.0 - o["momentum"]) * rho * gi, mom, g)
        params = jax.tree.map(
            lambda p, m, gi: p - o["outer_lr"] * (rho * gi
                                                  + o["momentum"] * m),
            params, mom, g)
        return params, mom

    def eval_mean(self, params) -> float:
        return float(np.mean([float(self._eval(params, jnp.asarray(b["tokens"]),
                                               jnp.asarray(b["labels"])))
                              for b in self.eval_set]))

    def run(self, n_steps: int) -> dict:
        """State after each of the first ``n_steps`` commits: the mean
        eval loss after every step, per-leaf norms of the momentum after
        step 1 and of the parameters' change after the last step, and
        per-leaf norms of the loss's gradient at the first inner step."""
        job, o = self.job, self.job["outer"]
        with jax.default_matmul_precision("highest"):
            params = self.arch.init_params(self.cfg, self.seed)
            p0 = jax.tree.map(np.asarray, params)
            mom = jax.tree.map(jnp.zeros_like, params)
            zeros = lambda: jax.tree.map(jnp.zeros_like, params)
            opts, efs, counts, starts = {}, {}, {}, {}
            rho = math.sqrt(self.n) / self.n
            lookahead = o.get("lookahead_init", True)

            def start(p, m):
                if not lookahead:
                    return p
                return jax.tree.map(
                    lambda a, b: a - o["outer_lr"] * o["momentum"] * b, p, m)

            for w in range(self.n):
                starts[w] = start(params, mom)
            out = {"loss": [], "names": leaf_names(params)}
            for k, group in enumerate(scheduled_steps(
                    job["worker_paces"], self.n, job["inner_steps"],
                    job["commit_batch"], n_steps)):
                for w, _s_i, _tau in group:
                    theta = starts[w]
                    p = theta
                    opt = opts.get(w) or (zeros(), zeros(),
                                          jnp.zeros((), jnp.int32))
                    c0 = counts.get(w, 0)
                    for hh in range(job["inner_steps"]):
                        b = worker_batch(self.specs, w % len(self.specs),
                                         job["batch_size"], job["seq_len"],
                                         self.seed * 977 + w, c0 + hh)
                        if "raw_grad" not in out:
                            out["raw_grad"] = leaf_norms(self._grad(
                                p, jnp.asarray(b["tokens"]),
                                jnp.asarray(b["labels"])))
                        p, opt = self._step(p, opt, jnp.asarray(b["tokens"]),
                                            jnp.asarray(b["labels"]))
                    opts[w], counts[w] = opt, c0 + job["inner_steps"]
                    delta = jax.tree.map(lambda a, b: a - b, theta, p)
                    if self.fault == "delta_altered":
                        leaves, tree = jax.tree.flatten(delta)
                        delta = tree.unflatten([2.0 * leaves[0]] + leaves[1:])
                    if o["compression"] == "int8":
                        target = delta if w not in efs else jax.tree.map(
                            jnp.add, delta, efs[w])
                        delta = jax.tree.map(int8_roundtrip, target)
                        if o["error_feedback"]:
                            efs[w] = jax.tree.map(jnp.subtract, target,
                                                  delta)
                    elif o["compression"] != "none":
                        raise ValueError(o["compression"])
                    if self.fault != "state_unchanged":
                        params, mom = self._commit(params, mom, delta,
                                                   jnp.float32(rho))
                for w, _s_i, _tau in group:
                    starts[w] = start(params, mom)
                out["loss"].append(self.eval_mean(params))
                if k == 0:
                    out["grad"] = leaf_norms(mom)
            out["change"] = leaf_norms(jax.tree.map(
                lambda a, b: np.asarray(a, np.float64) - b, params, p0))
        return out
