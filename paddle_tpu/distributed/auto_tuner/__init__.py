"""Auto-tuner: parallel-config search (parity: python/paddle/distributed/
auto_tuner/ — AutoTuner tuner.py:21, cost_model.py, prune.py).

TPU-native: candidate (dp, mp, pp, sharding, sep, micro-batch) configs are
enumerated over the chip count, pruned by divisibility/memory heuristics
(prune.py's rules), ranked by an analytic roofline cost model built on the
scaling-book math (MXU flops vs ICI collective bytes), and optionally
measured by running a user-provided trial function — the reference launches
whole trial jobs; on a single controller the trial is a jitted step."""

from __future__ import annotations

import itertools
import json
import math
from dataclasses import dataclass, field
from typing import Callable, List, Optional


@dataclass
class TunerConfig:
    dp_degree: int = 1
    mp_degree: int = 1
    pp_degree: int = 1
    sharding_degree: int = 1
    sep_degree: int = 1
    micro_batch_size: int = 1
    estimated_cost: float = 0.0
    measured_time: Optional[float] = None
    trial_error: Optional[str] = None

    def degrees(self):
        return (self.dp_degree, self.mp_degree, self.pp_degree,
                self.sharding_degree, self.sep_degree)

    def world(self):
        return math.prod(self.degrees())

    def to_dict(self):
        return {
            "dp_degree": self.dp_degree, "mp_degree": self.mp_degree,
            "pp_degree": self.pp_degree,
            "sharding_degree": self.sharding_degree,
            "sep_degree": self.sep_degree,
            "micro_batch_size": self.micro_batch_size,
            "estimated_cost": self.estimated_cost,
            "measured_time": self.measured_time,
        }


@dataclass
class ModelSpec:
    """What the cost model needs to know about the workload."""
    hidden_size: int = 1024
    num_layers: int = 12
    seq_len: int = 1024
    vocab_size: int = 50304
    global_batch_size: int = 8
    param_bytes: int = 2  # bf16


def _divisors(n):
    return [d for d in range(1, n + 1) if n % d == 0]


def generate_candidates(num_devices: int, model: ModelSpec,
                        max_mp: int = 8, max_pp: int = 8) -> List[TunerConfig]:
    """Enumerate degree tuples whose product == num_devices (tuner.py
    candidate generation)."""
    out = []
    for mp in _divisors(num_devices):
        if mp > max_mp:
            continue
        for pp in _divisors(num_devices // mp):
            if pp > max_pp:
                continue
            rest = num_devices // (mp * pp)
            for sh in _divisors(rest):
                for sep in _divisors(rest // sh):
                    dp = rest // (sh * sep)
                    for mbs in (1, 2, 4, 8):
                        if model.global_batch_size % max(dp * sh, 1):
                            continue
                        if (model.global_batch_size // max(dp * sh, 1)) % mbs:
                            continue
                        out.append(TunerConfig(dp, mp, pp, sh, sep, mbs))
    return out


def prune(candidates: List[TunerConfig], model: ModelSpec,
          hbm_bytes: float = 95e9) -> List[TunerConfig]:
    """Reject configs violating structural/memory constraints (prune.py)."""
    kept = []
    h = model.hidden_size
    n_params = (12 * h * h * model.num_layers
                + model.vocab_size * h)
    for c in candidates:
        # mp must divide the hidden/head dims; pp must divide layers
        if h % c.mp_degree or model.num_layers % c.pp_degree:
            continue
        if model.seq_len % c.sep_degree:
            continue
        # memory: params+grads+optimizer(2 moments fp32 + master fp32)
        shard = c.mp_degree * c.pp_degree * c.sharding_degree
        bytes_per_chip = n_params / shard * (
            model.param_bytes + model.param_bytes + 16)
        # activations per microbatch (rough: 20 * s * h * L / (pp*sep))
        act = (20 * model.seq_len * h * model.num_layers *
               c.micro_batch_size / (c.pp_degree * c.sep_degree))
        if bytes_per_chip + act > hbm_bytes:
            continue
        kept.append(c)
    return kept


def estimate_cost(c: TunerConfig, model: ModelSpec,
                  mxu_flops: float = 459e12, ici_bw: float = 1.2e11) -> float:
    """Roofline step-time estimate: compute time + exposed collective time
    (cost_model.py analogue, scaling-book arithmetic)."""
    h, L, s = model.hidden_size, model.num_layers, model.seq_len
    B = model.global_batch_size
    flops = 6 * (12 * h * h * L + model.vocab_size * h) * B * s  # fwd+bwd
    t_compute = flops / (mxu_flops * c.world())
    # tp collectives: 4 allreduces of b*s*h per layer over mp
    t_mp = 0.0
    if c.mp_degree > 1:
        bytes_mp = 4 * L * (B / max(c.dp_degree * c.sharding_degree, 1)) * \
            s / max(c.sep_degree, 1) * h * model.param_bytes
        t_mp = bytes_mp * 2 * (c.mp_degree - 1) / c.mp_degree / ici_bw
    # sep ring attention: each device rotates its K,V block (sep-1) hops
    t_sep = 0.0
    if c.sep_degree > 1:
        bytes_sep = 2 * L * (B / max(c.dp_degree * c.sharding_degree, 1)) * \
            (s / c.sep_degree) * h * model.param_bytes * (c.sep_degree - 1)
        t_sep = bytes_sep / ici_bw
    # dp grad allreduce (sharded -> reduce-scatter+allgather, same bytes)
    t_dp = 0.0
    if c.dp_degree * c.sharding_degree > 1:
        n_params = 12 * h * h * L + model.vocab_size * h
        t_dp = 2 * n_params * model.param_bytes / ici_bw
    # pp bubble: (pp-1)/(microbatches) of compute
    n_micro = max(B // max(c.dp_degree * c.sharding_degree, 1)
                  // c.micro_batch_size, 1)
    bubble = (c.pp_degree - 1) / (n_micro + c.pp_degree - 1)
    return (t_compute + t_mp + t_sep + t_dp) / max(1 - bubble, 1e-3)


def subprocess_trial_fn(model: ModelSpec, steps: int = 3,
                        timeout: float = 600.0,
                        trial_args: Optional[dict] = None):
    """Build a trial_fn that MEASURES a candidate by spawning a real trial
    job (reference: the tuner launches whole distributed jobs per
    candidate, tuner.py:21) on a virtual CPU mesh sized to the config's
    world — each trial is its own process with its own XLA device count,
    so compile failures/OOMs are isolated and simply score inf.
    """
    import os
    import subprocess
    import sys

    extra = trial_args or {}

    def run(cfg: TunerConfig) -> float:
        env = dict(os.environ)
        # trials are CPU-only, so a parent that has initialised JAX (and
        # holds the chip) can still start them
        env["JAX_PLATFORMS"] = "cpu"
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "host_platform_device_count" not in f]
        flags.append(
            f"--xla_force_host_platform_device_count={cfg.world()}")
        env["XLA_FLAGS"] = " ".join(flags)
        # invoke by FILE PATH: `-m` would import the paddle_tpu package
        # (and initialize the jax backend) before the trial can pin the
        # cpu platform + virtual device count
        trial_path = os.path.join(os.path.dirname(__file__), "trial.py")
        repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
        cmd = [sys.executable, trial_path,
               "--dp", str(cfg.dp_degree), "--mp", str(cfg.mp_degree),
               "--pp", str(cfg.pp_degree),
               "--sharding", str(cfg.sharding_degree),
               "--sep", str(cfg.sep_degree),
               "--micro-batch", str(cfg.micro_batch_size),
               "--hidden", str(extra.get("hidden", min(model.hidden_size, 64))),
               "--layers", str(extra.get("layers", min(model.num_layers, 2))),
               "--seq", str(extra.get("seq", min(model.seq_len, 32))),
               "--vocab", str(extra.get("vocab", min(model.vocab_size, 256))),
               "--steps", str(steps)]
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=timeout)
        if proc.returncode != 0:
            raise RuntimeError(
                f"trial {cfg.degrees()} failed rc={proc.returncode}: "
                f"{proc.stderr[-500:]}")
        for line in reversed(proc.stdout.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                payload = json.loads(line)
                if "measured_time_ms" in payload:
                    return float(payload["measured_time_ms"])
                raise RuntimeError(f"trial error: {payload}")
        raise RuntimeError(f"trial produced no result: {proc.stdout[-300:]}")

    return run


class AutoTuner:
    """tuner.py:21 parity: generate -> prune -> rank -> (optionally) measure."""

    def __init__(self, num_devices: int, model: ModelSpec,
                 trial_fn: Optional[Callable[[TunerConfig], float]] = None,
                 max_trials: int = 8):
        self.num_devices = num_devices
        self.model = model
        self.trial_fn = trial_fn
        self.max_trials = max_trials
        self.history: List[TunerConfig] = []

    def search(self) -> TunerConfig:
        cands = prune(generate_candidates(self.num_devices, self.model),
                      self.model)
        if not cands:
            raise RuntimeError("no feasible parallel config after pruning")
        for c in cands:
            c.estimated_cost = estimate_cost(c, self.model)
        cands.sort(key=lambda c: c.estimated_cost)
        if self.trial_fn is None:
            self.history = cands
            return cands[0]
        best, best_t = None, float("inf")
        for c in cands[: self.max_trials]:
            try:
                c.measured_time = float(self.trial_fn(c))
            except Exception as e:  # failed trial scores inf, reason kept
                c.measured_time = float("inf")
                c.trial_error = f"{type(e).__name__}: {e}"[:500]
            self.history.append(c)
            if c.measured_time < best_t:
                best, best_t = c, c.measured_time
        if best is None:  # every trial failed: fall back to estimated best
            best = cands[0]
        return best
