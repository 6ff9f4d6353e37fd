"""GBLinear: elastic-net linear booster via shotgun coordinate descent.

Re-implements the reference ``GBLinear`` (``src/gbm/gblinear-inl.hpp``):
per-round bias Newton step (``CalcDeltaBias``, :224-227) followed by
per-feature elastic-net coordinate updates (``CalcDelta`` soft threshold,
:213-225), with ``num_output_group`` weight columns for multiclass.

TPU-native shape: the reference's shotgun CD runs features in parallel
OMP threads over a SHARED gradient vector that absorbs each thread's
updates as they land (:76-105 — Shotgun/Bradley et al.), so correlated
features see each other's progress.  A fully-synchronous Jacobi step
(all features against the same stale residual) loses that property and
DIVERGES on strongly correlated features.  Here one boosting round is a
jitted ``lax.scan`` over feature blocks: within a block, deltas are
computed in parallel (MXU reductions); between blocks the residual
gradient is updated exactly (``g += h * X_b @ delta_b`` — the same
algebra as the reference's in-place ``p.grad += p.hess * v * dw``).
Block size 1 (the default) is exact sequential coordinate descent;
larger blocks trade shotgun-style parallelism for the (bounded)
correlation risk the reference accepts.  Missing entries contribute 0,
matching the reference's sparse column iteration.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from xgboost_tpu.config import TrainParam
from xgboost_tpu.data import DMatrix


@functools.partial(jax.jit, static_argnames=(
    "eta", "lam", "alpha", "lam_bias", "block", "axis_name"))
def _linear_boost_step(X, gh, weight, bias, eta, lam, alpha, lam_bias,
                       block=1, axis_name=None):
    """One round of bias + block-sequential coordinate updates.

    X: (N, F) dense (0 = missing); gh: (N, K, 2); weight: (F, K); bias: (K,).
    With ``axis_name`` (dsplit=row: rows sharded over a mesh axis), every
    row reduction — the bias sums and each block's ``Gf``/``Hf`` — psums
    over the axis, exactly where the reference would allreduce
    (gblinear-inl.hpp:45-106 runs on the local shard; the distributed
    completion is VERDICT r2 item 10).  The residual update stays
    shard-local (rows only see their own delta effect).
    """
    red = (lambda x: jax.lax.psum(x, axis_name)) if axis_name else \
        (lambda x: x)
    g, h = gh[..., 0], gh[..., 1]            # (N, K)
    # bias step (CalcDeltaBias)
    sum_g, sum_h = red(g.sum(axis=0)), red(h.sum(axis=0))
    dbias = eta * (-(sum_g + lam_bias * bias) / (sum_h + lam_bias + 1e-12))
    bias = bias + dbias
    g = g + h * dbias[None, :]               # remove bias effect (ref :66-73)

    F = X.shape[1]
    bf = max(1, min(block, F))
    n_blocks = -(-F // bf)
    f_pad = n_blocks * bf
    if f_pad != F:
        X = jnp.pad(X, ((0, 0), (0, f_pad - F)))
        weight = jnp.pad(weight, ((0, f_pad - F), (0, 0)))

    def body(carry, b):
        g, weight = carry
        Xb = jax.lax.dynamic_slice_in_dim(X, b * bf, bf, 1)       # (N, bf)
        wb = jax.lax.dynamic_slice_in_dim(weight, b * bf, bf, 0)  # (bf, K)
        Gf = red(Xb.T @ g)                   # (bf, K)
        Hf = red((Xb * Xb).T @ h)
        # CalcDelta elastic-net step (ref :213-225)
        tmp = wb - (Gf + lam * wb) / (Hf + lam)
        pos = -(Gf + lam * wb + alpha) / (Hf + lam)
        neg = -(Gf + lam * wb - alpha) / (Hf + lam)
        delta = jnp.where(tmp >= 0, jnp.maximum(pos, -wb),
                          jnp.minimum(neg, -wb))
        delta = jnp.where(Hf < 1e-5, 0.0, eta * delta)
        weight = jax.lax.dynamic_update_slice_in_dim(
            weight, wb + delta, b * bf, 0)
        # exact residual propagation to later blocks (ref :96-99)
        g = g + h * (Xb @ delta)
        return (g, weight), None

    (g, weight), _ = jax.lax.scan(body, (g, weight),
                                  jnp.arange(n_blocks))
    return weight[:F], bias


@functools.lru_cache(maxsize=None)
def _linear_boost_step_dp_fn(mesh, eta, lam, alpha, lam_bias, block):
    """Compiled row-sharded boosting step, cached per (mesh, params) so
    per-round calls hit the jit cache instead of re-tracing (meshes are
    hashable; floats come in already-coerced)."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    fn = shard_map(
        functools.partial(
            _linear_boost_step.__wrapped__, eta=eta, lam=lam, alpha=alpha,
            lam_bias=lam_bias, block=block, axis_name="data"),
        mesh=mesh, in_specs=(P("data"), P("data"), P(), P()),
        out_specs=(P(), P()), check_vma=False)
    return jax.jit(fn)


def _linear_boost_step_dp(mesh, X, gh, weight, bias, eta, lam, alpha,
                          lam_bias, block=1):
    """Row-sharded boosting round: X/gh sharded over 'data', weight/bias
    replicated; reductions psum over the mesh (bit-matches single-device
    up to reduction order)."""
    return _linear_boost_step_dp_fn(mesh, eta, lam, alpha, lam_bias,
                                    block)(X, gh, weight, bias)


@jax.jit
def _linear_predict(X, weight, bias, base):
    return base + bias[None, :] + X @ weight


class GBLinear:
    """Linear booster state (reference gblinear-inl.hpp Model, :228-278)."""

    def __init__(self, param: TrainParam, num_feature: int):
        self.param = param
        self.num_feature = num_feature
        K = max(1, param.num_output_group)
        self.weight = jnp.zeros((num_feature, K), jnp.float32)
        self.bias = jnp.zeros((K,), jnp.float32)
        self.version = 0  # boosting rounds applied

    @property
    def num_boosted_rounds(self) -> int:
        return self.version

    def host_matrix(self, dmat: DMatrix) -> np.ndarray:
        """Dense (N, F) host matrix, 0 for missing entries."""
        X = dmat.to_dense(missing=np.nan)
        if X.shape[1] < self.num_feature:
            X = np.pad(X, ((0, 0), (0, self.num_feature - X.shape[1])),
                       constant_values=np.nan)
        return np.nan_to_num(X[:, :self.num_feature], nan=0.0)

    def device_matrix(self, dmat: DMatrix) -> jax.Array:
        return jnp.asarray(self.host_matrix(dmat))

    def do_boost(self, X: jax.Array, gh: jax.Array, info=None,
                 mesh=None) -> None:
        if mesh is not None:
            self.weight, self.bias = _linear_boost_step_dp(
                mesh, X, gh, self.weight, self.bias,
                float(self.param.eta), float(self.param.reg_lambda),
                float(self.param.reg_alpha), float(self.param.lambda_bias),
                block=max(1, self.param.linear_block))
        else:
            self.weight, self.bias = _linear_boost_step(
                X, gh, self.weight, self.bias,
                float(self.param.eta), float(self.param.reg_lambda),
                float(self.param.reg_alpha), float(self.param.lambda_bias),
                block=max(1, self.param.linear_block))
        self.version += 1

    def predict_margin(self, X: jax.Array, base, ntree_limit: int = 0,
                       root=None):
        # root (multi-root trees) has no meaning for a linear model
        return _linear_predict(X, self.weight, self.bias,
                               jnp.asarray(base, jnp.float32))

    def predict_leaf(self, X, ntree_limit: int = 0, root=None):
        raise ValueError("pred_leaf is not defined for the gblinear booster")

    # ------------------------------------------------------------ serialize
    def get_state(self) -> dict:
        return {"linear_weight": np.asarray(self.weight),
                "linear_bias": np.asarray(self.bias),
                "linear_version": np.int64(self.version)}

    @classmethod
    def from_state(cls, param: TrainParam, state: dict) -> "GBLinear":
        w = state["linear_weight"]
        m = cls(param, w.shape[0])
        m.weight = jnp.asarray(w)
        m.bias = jnp.asarray(state["linear_bias"])
        m.version = int(state.get("linear_version", 1))
        return m

    def dump_text(self) -> str:
        """Text dump (reference GBLinear::DumpModel, gblinear-inl.hpp:127-142)."""
        lines = ["bias:"]
        lines += [f"{float(b):g}" for b in np.asarray(self.bias)]
        lines.append("weight:")
        for row in np.asarray(self.weight):
            lines += [f"{float(v):g}" for v in row]
        return "\n".join(lines) + "\n"
