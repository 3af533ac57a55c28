"""Search objectives: the paper's loss (Eqn. 23) behind a pluggable protocol.

The paper optimizes ``CE(X, quant(θ)) + α · MSE(H, H₀)`` (Algorithm 1's
listing uses an L_KL variant); the search loop itself only ever consumes a
scalar, so both live behind a first-class :class:`Objective` protocol:

- ``prepare(env) → state``      once-per-run precomputation (reference
  projections, saliency weights, …) from the frozen :class:`ObjectiveEnv`;
- ``evaluate(logits, hidden, state, env) → (primary, aux)``   the traced
  per-candidate scalar pair; the engine combines them as
  ``loss = primary + α · aux``;
- ``resolve_mix(p0, a0, env) → α``   the mixing weight from the step-0
  values (§4.1 resolves α so CE is ``ce_weight``× more important at start);
- ``metrics() → dict``          static labels for the obs registry rows.

Built-ins (see ``OBJECTIVES`` / :func:`get_objective`):

- ``"ce"``           Eqn. 23, the default — bit-for-bit the legacy loss;
- ``"kl"``           the Algorithm-1 listing's label-free KL variant;
- ``"swd_actmatch"`` sliced-Wasserstein alignment of tapped activations
  (random-projection 1-D Wasserstein, PAPERS.md: Cao/Yin/Aref 2026);
- ``"saliency_ce"``  per-token CE weighted by the FP model's confidence in
  the true token (PAPERS.md: Cao/Aref 2025).

``SearchConfig.objective`` accepts a registry name or an ``Objective``
instance; the loose functions (``calib_ce`` …) remain exported for direct
use and for the legacy-parity test's verbatim transcription.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Union

import jax
import jax.numpy as jnp

from repro.models.model import lm_loss

__all__ = ["calib_ce", "calib_kl", "activation_mse", "resolve_alpha",
           "ObjectiveEnv", "Objective", "CEObjective", "KLObjective",
           "SWDActMatchObjective", "SaliencyCEObjective", "OBJECTIVES",
           "register_objective", "get_objective", "objective_name"]


def calib_ce(logits, tokens, vocab_size: int):
    """Next-token cross-entropy on the calibration batch."""
    return lm_loss(logits[:, :-1], tokens[:, 1:], vocab_size)


def calib_kl(logits_q, logits_fp, vocab_size: int):
    """KL(p_fp || p_q) averaged over positions."""
    V = logits_q.shape[-1]
    if V > vocab_size:
        mask = jnp.arange(V) < vocab_size
        neg = jnp.finfo(jnp.float32).min / 2
        logits_q = jnp.where(mask, logits_q, neg)
        logits_fp = jnp.where(mask, logits_fp, neg)
    lq = jax.nn.log_softmax(logits_q.astype(jnp.float32), axis=-1)
    lp = jax.nn.log_softmax(logits_fp.astype(jnp.float32), axis=-1)
    p = jnp.exp(lp)
    return jnp.mean(jnp.sum(p * (lp - lq), axis=-1))


def activation_mse(hidden_q, hidden_fp, n_match: int):
    """MSE over the first ``n_match`` per-layer block outputs.

    hidden_*: (L, B, S, D) stacks from forward(collect_hidden=True).
    n_match == 0 disables activation matching (paper Table 4, '0 layers').
    """
    if n_match == 0:
        return jnp.float32(0.0)
    hq = hidden_q[:n_match].astype(jnp.float32)
    hf = hidden_fp[:n_match].astype(jnp.float32)
    return jnp.mean(jnp.square(hq - hf))


def _act_mse(hidden, env) -> jnp.ndarray:
    """The objectives' activation-MSE term (0 with matching off), under the
    ``objective.act_mse`` scope that names its device operations."""
    if not env.n_match:
        return jnp.float32(0.0)
    with jax.named_scope("objective.act_mse"):
        return activation_mse(hidden, env.hidden_fp, env.n_match)


def resolve_alpha(ce0: float, mse0: float, ce_weight: float = 10.0) -> float:
    """Paper §4.1: α chosen so CE is ``ce_weight``× more important than the
    activation MSE at the start of the search."""
    if mse0 <= 0:
        return 0.0
    return float(ce0 / (ce_weight * mse0))


# ---------------------------------------------------------------------------
# The Objective protocol
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ObjectiveEnv:
    """Everything an objective may read, fixed for one engine run (one island
    under sharded calibration): the calibration slice, the FP reference
    forward on that slice, and the paper's matching hyper-parameters."""

    calib: Any                    # (B, S) int tokens
    logits_fp: Any                # (B, S, V) FP reference logits
    hidden_fp: Any                # (n_match, B, S, D) FP taps, or None
    vocab_size: int
    n_match: int
    ce_weight: float = 10.0


class Objective:
    """Base protocol; subclasses override the four hooks below.

    ``evaluate`` runs inside the engine's jitted candidate-eval program, so
    it must be pure and shape-stable; ``prepare`` runs once on the host and
    may allocate reference state (device arrays welcome — they are closed
    over by the jitted program).
    """

    name = "objective"

    def prepare(self, env: ObjectiveEnv) -> Any:
        return None

    def evaluate(self, logits, hidden, state, env: ObjectiveEnv):
        raise NotImplementedError

    def resolve_mix(self, primary0: float, aux0: float,
                    env: ObjectiveEnv) -> float:
        return 0.0

    def metrics(self) -> Dict[str, str]:
        return {"objective": self.name}


class CEObjective(Objective):
    """Eqn. 23: calibration CE + α · activation MSE — the paper default.

    The traced graph is primitive-for-primitive the legacy engine's, which
    is what keeps the pop=1/isl=1/T=0 trajectory bit-for-bit."""

    name = "ce"

    def evaluate(self, logits, hidden, state, env: ObjectiveEnv):
        with jax.named_scope("objective.ce"):
            primary = calib_ce(logits, env.calib, env.vocab_size)
        return primary, _act_mse(hidden, env)

    def resolve_mix(self, primary0, aux0, env):
        return resolve_alpha(primary0, aux0, env.ce_weight) \
            if env.n_match else 0.0


class KLObjective(CEObjective):
    """Algorithm-1 listing: KL(p_fp || p_q) + α · activation MSE."""

    name = "kl"

    def evaluate(self, logits, hidden, state, env: ObjectiveEnv):
        with jax.named_scope("objective.ce"):
            primary = calib_kl(logits, env.logits_fp, env.vocab_size)
        return primary, _act_mse(hidden, env)


def _swd_1d(x_sorted, y):
    """1-D Wasserstein-2² between pre-sorted reference projections and a new
    sample set: sort y, mean squared quantile difference."""
    return jnp.mean(jnp.square(jnp.sort(y, axis=0) - x_sorted))


class SWDActMatchObjective(Objective):
    """Sliced-Wasserstein activation alignment (PAPERS.md 2601.07878).

    Project the tapped activations of the quantized and FP models onto
    ``n_proj`` fixed random directions, sort each 1-D cloud, and average the
    squared quantile differences — a distributional match that, unlike the
    pointwise MSE, tolerates token-position reshuffling while still pinning
    the activation geometry. With ``n_match == 0`` the logits cloud is
    matched instead (data-free variant). ``aux`` is the calibration CE so
    ``resolve_mix`` can anchor the scale the same way the paper anchors α.
    """

    name = "swd_actmatch"

    def __init__(self, n_proj: int = 64, proj_seed: int = 0,
                 ce_anchor: bool = True):
        self.n_proj = int(n_proj)
        self.proj_seed = int(proj_seed)
        self.ce_anchor = bool(ce_anchor)

    def _features(self, hidden, env: ObjectiveEnv):
        if env.n_match and hidden is not None:
            h = hidden[:env.n_match].astype(jnp.float32)
            return h.reshape(env.n_match, -1, h.shape[-1])    # (L, N, D)
        return None

    def prepare(self, env: ObjectiveEnv):
        key = jax.random.PRNGKey(self.proj_seed)
        feats = self._features(env.hidden_fp, env)
        if feats is None:   # data-free fallback: match the logits cloud
            ref = env.logits_fp.astype(jnp.float32)
            ref = ref.reshape(1, -1, ref.shape[-1])
            feats = ref
        d = feats.shape[-1]
        dirs = jax.random.normal(key, (d, self.n_proj), jnp.float32)
        dirs = dirs / (jnp.linalg.norm(dirs, axis=0, keepdims=True) + 1e-12)
        # (L, N, n_proj) reference projections, pre-sorted along samples
        ref_sorted = jnp.sort(feats @ dirs, axis=1)
        return {"dirs": jax.lax.stop_gradient(dirs),
                "ref_sorted": jax.lax.stop_gradient(ref_sorted)}

    def evaluate(self, logits, hidden, state, env: ObjectiveEnv):
        feats = self._features(hidden, env)
        if feats is None:
            lg = logits.astype(jnp.float32)
            feats = lg.reshape(1, -1, lg.shape[-1])
        proj = feats @ state["dirs"]                          # (L, N, n_proj)
        swd = jax.vmap(_swd_1d)(state["ref_sorted"], proj).mean()
        with jax.named_scope("objective.ce"):
            aux = (calib_ce(logits, env.calib, env.vocab_size)
                   if self.ce_anchor else jnp.float32(0.0))
        return swd, aux

    def resolve_mix(self, primary0, aux0, env):
        # anchor: the CE term starts 1/ce_weight as important as the SWD
        if not self.ce_anchor or aux0 <= 0:
            return 0.0
        return float(primary0 / (env.ce_weight * aux0))

    def metrics(self):
        return {"objective": self.name, "n_proj": str(self.n_proj)}


class SaliencyCEObjective(Objective):
    """Saliency-weighted CE (PAPERS.md 2504.13932): per-token NLL weighted by
    the FP model's probability of the true token, so tokens the full-precision
    model is confident about dominate the search signal while tokens it
    already gets wrong cannot drag the climb. Weights are normalized to mean
    1 over valid positions (the unweighted CE is the all-ones special case);
    ``aux`` is the paper's activation MSE, mixed exactly like ``"ce"``."""

    name = "saliency_ce"

    def __init__(self, temperature: float = 1.0):
        self.temperature = float(temperature)

    def prepare(self, env: ObjectiveEnv):
        lp = jax.nn.log_softmax(
            env.logits_fp[:, :-1].astype(jnp.float32) / self.temperature,
            axis=-1)
        labels = env.calib[:, 1:]
        p_true = jnp.take_along_axis(
            jnp.exp(lp), labels[..., None], axis=-1)[..., 0]
        w = p_true / jnp.maximum(jnp.mean(p_true), 1e-9)
        return {"w": jax.lax.stop_gradient(w)}

    def evaluate(self, logits, hidden, state, env: ObjectiveEnv):
        with jax.named_scope("objective.ce"):
            lg = logits[:, :-1]
            labels = env.calib[:, 1:]
            V = lg.shape[-1]
            if V > env.vocab_size:
                mask = jnp.arange(V) < env.vocab_size
                neg = jnp.finfo(jnp.float32).min / 2
                lg = jnp.where(mask[None, None, :], lg, neg)
            logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
            nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
            primary = jnp.mean(nll * state["w"])
        return primary, _act_mse(hidden, env)

    def resolve_mix(self, primary0, aux0, env):
        return resolve_alpha(primary0, aux0, env.ce_weight) \
            if env.n_match else 0.0

    def metrics(self):
        return {"objective": self.name,
                "saliency_temperature": str(self.temperature)}


# ---------------------------------------------------------------------------
# Registry: string names <-> Objective instances
# ---------------------------------------------------------------------------

OBJECTIVES: Dict[str, Callable[[], Objective]] = {
    "ce": CEObjective,
    "kl": KLObjective,
    "swd_actmatch": SWDActMatchObjective,
    "saliency_ce": SaliencyCEObjective,
}


def register_objective(name: str, factory: Callable[[], Objective],
                       overwrite: bool = False) -> None:
    """Register a custom objective factory under ``name`` (what
    ``SearchConfig.objective`` strings resolve through)."""
    if name in OBJECTIVES and not overwrite:
        raise ValueError(f"objective {name!r} already registered")
    OBJECTIVES[name] = factory


def get_objective(spec: Union[str, Objective, None]) -> Objective:
    """Resolve ``SearchConfig.objective``: a registry name, an ``Objective``
    instance (returned as-is), or None (the default CE objective)."""
    if spec is None:
        return CEObjective()
    if isinstance(spec, Objective):
        return spec
    if isinstance(spec, str):
        try:
            return OBJECTIVES[spec]()
        except KeyError:
            raise ValueError(
                f"unknown objective {spec!r}; registered: "
                f"{sorted(OBJECTIVES)}") from None
    raise TypeError(
        f"objective must be a name or an Objective, got {type(spec).__name__}")


def objective_name(spec: Union[str, Objective, None]) -> str:
    """The stats/metrics label for an objective spec without instantiating
    twice (names are stable identity for registry round-trips)."""
    if spec is None:
        return "ce"
    if isinstance(spec, Objective):
        return spec.name
    return str(spec)
