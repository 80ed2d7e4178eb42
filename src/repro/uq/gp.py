"""Gaussian-process emulation: the paper's offline coarsest-level GP (§4.3)
plus an ONLINE sliding-window variant that the surrogate-accelerated DA
screen trains from evaluation-fabric traffic.

`GP` — exact GP with constant mean, Matérn-5/2 ARD covariance, (near-)
noise-free Gaussian likelihood; hyperparameters by Type-II maximum
likelihood (Adam on the log-marginal-likelihood via jax AD — matching the
paper's setup of 'constant mean, Matérn-5/2 ARD, noise-free likelihood,
Type-II MLE').

`OnlineGP` — the same model refit incrementally on a sliding window of
streamed (theta, y) pairs: refits re-factorize the window from scratch
(Cholesky-DOWNDATE-FREE — at screen-sized windows a fresh O(n^3)
factorization is cheaper and unconditionally stable, where rank-1 downdates
lose positive-definiteness to round-off), and the expensive Type-II MLE
hyperparameter search re-runs only on a predictive-error STALENESS trigger.
`uq.surrogate.SurrogateStore` is the fabric tap that feeds it.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.races import named_rlock
from repro.core import tracing
from repro.core.interface import next_pow2, pad_to_bucket

#: predictive-variance floor relative to the kernel amplitude — the Schur
#: complement amp - v^T v is computed by subtraction, so near-degenerate
#: training sets return small NEGATIVE variances through round-off, and a
#: screen that takes log/sqrt/1-over of the variance NaNs on them
_VAR_REL_FLOOR = 1e-9


#: Cody-Waite split of ln 2 (k * _LN2_HI is exact in float32 for |k| < 2**15)
#: and the Cephes `expf` polynomial on [-ln2 / 2, ln2 / 2]
_LN2_HI, _LN2_LO = 0.693359375, -2.12194440e-4
_EXPF_POLY = (1.9875691500e-4, 1.3981999507e-3, 8.3334519073e-3,
              4.1665795894e-2, 1.6666665459e-1, 5.0000001201e-1)


@jax.custom_jvp
def _exp(x):
    """e**x to about one float32 ulp on every backend. The TPU's own float32
    `exp` is off by up to 4.3e-6 relative (v5e, against 7e-8 on the CPU);
    a near-noise-free GP's `alpha` is large (an l1 norm of 1e4 at 128
    training points) and carries kernel errors of that size into means
    several noise sd off. Other dtypes take `jnp.exp`."""
    if x.dtype != jnp.float32:
        return jnp.exp(x)
    x = jnp.clip(x, -87.0, 88.0)  # 2**k stays a normal float32
    k = jnp.floor(x * np.float32(1.4426950408889634) + 0.5)
    f = x - k * _LN2_HI - k * _LN2_LO
    p = jnp.float32(0.0)
    for c in _EXPF_POLY:
        p = p * f + c
    two_k = jax.lax.bitcast_convert_type((k.astype(jnp.int32) + 127) << 23, jnp.float32)
    return (p * f * f + f + 1.0) * two_k


@_exp.defjvp
def _exp_jvp(primals, tangents):
    y = _exp(primals[0])
    return y, y * tangents[0]


def _matern52(X1, X2, lengthscales, amp):
    d = (X1[:, None, :] - X2[None, :, :]) / lengthscales
    r2 = jnp.sum(d * d, axis=-1)
    r = jnp.sqrt(r2 + 1e-12)
    s5r = jnp.sqrt(5.0) * r
    return amp * (1.0 + s5r + 5.0 * r2 / 3.0) * _exp(-s5r)


#: the cross-kernel as ONE compiled device program, fed padded blocks
#: (`_padded`) so a shape compiles once per power-of-two bucket
_kernel = jax.jit(_matern52)

#: smallest query block: single-point queries share one program
_Q_MIN = 8


def _padded(X: np.ndarray, least: int = 1) -> np.ndarray:
    """[N, d] float32 rows padded to the next power of two (>= `least`).
    Padded rows only meet padded kernel rows or columns, which are sliced
    off: every returned entry is exact."""
    return pad_to_bucket(X, max(least, next_pow2(len(X))))[0]


def _nlml(log_params, X, y):
    n, d = X.shape
    ls = jnp.exp(log_params[:d])
    amp = jnp.exp(log_params[d])
    noise = jnp.exp(log_params[d + 1])
    mean = log_params[d + 2]
    # jitter scales with amp: keeps K PD in fp32 even when a lengthscale
    # grows unbounded (irrelevant input dim -> K tends to rank-1)
    K = _matern52(X, X, ls, amp) + (noise + 1e-5 * amp + 1e-8) * jnp.eye(n)
    L = jnp.linalg.cholesky(K)
    r = y - mean
    alpha = jax.scipy.linalg.cho_solve((L, True), r)
    return (
        0.5 * r @ alpha
        + jnp.sum(jnp.log(jnp.diag(L)))
        + 0.5 * n * jnp.log(2 * jnp.pi)
    )


def _chol64(K: np.ndarray) -> np.ndarray:
    """float64 Cholesky with escalating jitter: online sliding windows can
    be near-duplicate-degenerate, and a failed factorization must not kill
    the sampler the GP screens for."""
    scale = float(np.mean(np.diag(K))) or 1.0
    jit = 0.0
    for _ in range(4):
        try:
            return np.linalg.cholesky(K + jit * np.eye(len(K)))
        except np.linalg.LinAlgError:
            jit = max(jit * 100.0, 1e-8 * scale)
    raise np.linalg.LinAlgError("kernel matrix not PD even with jitter")


@dataclass
class GP:
    X: np.ndarray
    y: np.ndarray
    log_params: np.ndarray  # [d lengthscales, amp, noise, mean]
    _chol: np.ndarray
    _alpha: np.ndarray

    @classmethod
    def fit(
        cls,
        X: np.ndarray,
        y: np.ndarray,
        n_iters: int = 400,
        lr: float = 0.05,
        noise_floor: float = 1e-6,
        seed: int = 0,
    ) -> "GP":
        X = jnp.asarray(np.atleast_2d(X), jnp.float32)
        yn = np.asarray(y, np.float32).ravel()
        y_mu, y_sd = float(yn.mean()), float(yn.std() + 1e-12)
        ys = jnp.asarray((yn - y_mu) / y_sd)
        n, d = X.shape
        span = jnp.asarray(np.ptp(np.asarray(X), axis=0) + 1e-6)
        p0 = jnp.concatenate(
            [jnp.log(span / 3.0), jnp.array([0.0, np.log(noise_floor), 0.0])]
        )
        val_grad = jax.jit(jax.value_and_grad(lambda p: _nlml(p, X, ys)))
        # Adam with box constraints + non-finite-step guard
        lo = jnp.concatenate([jnp.log(span) - 6.0, jnp.array([-6.0, np.log(1e-8), -3.0])])
        hi = jnp.concatenate([jnp.log(span) + 4.0, jnp.array([4.0, np.log(1e-2), 3.0])])
        p = p0
        m = jnp.zeros_like(p)
        v = jnp.zeros_like(p)
        for i in range(n_iters):
            _, g = val_grad(p)
            if not bool(jnp.all(jnp.isfinite(g))):
                break  # keep the last finite iterate
            m = 0.9 * m + 0.1 * g
            v = 0.999 * v + 0.001 * g * g
            mh = m / (1 - 0.9 ** (i + 1))
            vh = v / (1 - 0.999 ** (i + 1))
            p = jnp.clip(p - lr * mh / (jnp.sqrt(vh) + 1e-8), lo, hi)
        return cls.from_params(np.asarray(X), yn, np.asarray(p))

    @classmethod
    def from_params(cls, X: np.ndarray, y: np.ndarray, log_params) -> "GP":
        """Factorize a training set under FIXED hyperparameters — the
        online sliding-window refit path: no Adam loop, ONE Cholesky (and
        no rank-1 downdates when the window slides — re-factorizing is
        unconditionally stable and cheaper at screen-sized windows)."""
        X = np.atleast_2d(np.asarray(X, np.float32))
        yn = np.asarray(y, np.float32).ravel()
        y_mu, y_sd = float(yn.mean()), float(yn.std() + 1e-12)
        ys = (yn - y_mu) / y_sd
        d = X.shape[1]
        p = np.asarray(log_params, float)
        ls, amp, noise = np.exp(p[:d]), float(np.exp(p[d])), float(np.exp(p[d + 1]))
        # training inputs and hyperparameters live on the device from here:
        # a prediction uploads only its query block
        Xd = jax.device_put(_padded(X))
        lsd, ampd = jax.device_put(np.float32(ls)), jax.device_put(np.float32(amp))
        n = len(X)
        K = np.asarray(_kernel(Xd, Xd, lsd, ampd), np.float64)[:n, :n] + (
            noise + 1e-5 * amp + 1e-8) * np.eye(n)
        L = _chol64(K)
        alpha = np.linalg.solve(L.T, np.linalg.solve(L, np.asarray(ys - p[d + 2], np.float64)))
        gp = cls(X, yn, p, L, alpha)
        gp._ymu, gp._ysd = y_mu, y_sd
        gp._Xd, gp._lsd, gp._ampd = Xd, lsd, ampd
        return gp

    def predict(self, Xq: np.ndarray, return_var: bool = False):
        """Posterior mean (and variance) at Xq [Q, d] — one batched
        linear-algebra call for the whole query block, zero model waves.

        The float32 cross-kernel is one device program on the query block
        padded to a power of two (`padded` rows on the span); the mean
        matvec and the variance solve stay float64 on the host, since
        `_alpha` from a near-noise-free Cholesky is large and cancels.

        The predictive variance is clamped at a strictly positive floor
        (relative to the kernel amplitude): round-off in the Schur
        complement can return slightly negative values on near-degenerate
        training sets, and anything downstream that takes log/sqrt/1-over
        of the variance must stay finite."""
        Xq = np.atleast_2d(np.asarray(Xq, np.float32))
        Q, n = len(Xq), len(self.X)
        Xqp = _padded(Xq, _Q_MIN)
        with tracing.span("gp.predict", points=Q, padded=len(Xqp) - Q):
            d = self.X.shape[1]
            amp = np.exp(self.log_params[d])
            mean_c = self.log_params[d + 2]
            Ks = np.asarray(tracing.wait(
                _kernel(Xqp, self._Xd, self._lsd, self._ampd)))[:Q, :n]
            mu = mean_c + Ks @ self._alpha
            mu = self._ymu + self._ysd * mu
            if not return_var:
                return mu
            v = np.linalg.solve(self._chol, Ks.T)
            var = amp - np.sum(v * v, axis=0)
            var = np.maximum(var, _VAR_REL_FLOOR * float(amp) + 1e-300)
            return mu, var * self._ysd**2


class OnlineGP:
    """Batch-native GP trained ONLINE from streamed (theta, y) pairs — the
    level-(-1) surrogate behind `ensemble_mlda(surrogate=...)`.

    Three disciplines keep it cheap enough to sit inside a sampler loop:

      * **sliding window** — the newest `window` observations form the
        training set; `add()` only appends and marks the fit dirty.
      * **incremental, downdate-free refits** — the Cholesky factorization
        refreshes lazily at the next `predict_batch`, and at most once per
        `refit_every` absorbed points, by re-factorizing the window under
        the CURRENT hyperparameters (`GP.from_params`): a training burst
        costs one O(n^3) factorization, not one per wave, and no rank-1
        downdate ever risks losing positive-definiteness.
      * **staleness-triggered hyperparameter refits** — each incoming batch
        is first SCORED against the current fit; when the EWMA of the
        standardized predictive error |y - mu|/sd exceeds `stale_z`, the
        next refit re-runs the full Type-II MLE search (`GP.fit`) instead
        of reusing hyperparameters (a drifting target, or a window that
        outgrew its lengthscales, trips it).

    `predict_batch` serves the whole [Q, d] query block as ONE batched
    linear-algebra call with a strictly positive variance guarantee (see
    `GP.predict`), and `freeze()` stops ingestion/refitting for strict
    time-homogeneity once a DA screen must provably stop adapting.
    Thread-safe: the fabric training tap feeds `add` from collector
    threads while the sampler calls `predict_batch`.
    """

    def __init__(
        self,
        window: int = 256,
        min_train: int = 16,
        refit_every: int = 32,
        hyper_iters: int = 150,
        stale_z: float = 3.0,
        ewma_alpha: float = 0.2,
        seed: int = 0,
    ):
        self.window = int(window)
        self.min_train = max(2, int(min_train))
        self.refit_every = max(1, int(refit_every))
        self.hyper_iters = int(hyper_iters)
        self.stale_z = float(stale_z)
        self.ewma_alpha = float(ewma_alpha)
        self.seed = int(seed)
        self.frozen = False
        self._X: np.ndarray | None = None  # [n, d] sliding window
        self._y: np.ndarray | None = None
        self._gp: GP | None = None
        self._since_refit = 0  # points absorbed since the last factorization
        self._hyper_stale = True  # first fit IS the hyperparameter search
        self.err_ewma: float | None = None
        self.n_seen = 0
        self.n_hyper_fits = 0
        self.n_chol_refits = 0
        self._lock = named_rlock("online_gp")

    def __len__(self) -> int:
        return 0 if self._y is None else len(self._y)

    @property
    def ready(self) -> bool:
        """Whether `predict_batch` can serve (window >= min_train)."""
        with self._lock:
            return self._gp is not None or len(self) >= self.min_train

    def freeze(self) -> None:
        """Stop ingesting and (after at most one pending lazy refit)
        refitting — the fit becomes time-homogeneous, so a DA screen built
        on it is a fixed Markov kernel from here on."""
        with self._lock:
            self.frozen = True

    def add(self, X: np.ndarray, y: np.ndarray) -> None:
        """Absorb a streamed (theta [N, d], y [N]) block into the window.
        Non-finite targets are dropped (a diverged solve must not poison
        the emulator). No factorization happens here — refits are lazy and
        batched (see class docstring)."""
        X = np.atleast_2d(np.asarray(X, float))
        y = np.asarray(y, float).ravel()
        keep = np.isfinite(y) & np.all(np.isfinite(X), axis=1)
        if not keep.any():
            return
        X, y = X[keep], y[keep]
        with self._lock:
            gp = None if self.frozen else self._gp
        z = None
        if gp is not None:
            # staleness probe: score the incoming batch BEFORE absorbing —
            # against a snapshot, OUTSIDE the lock, so the kernel solves
            # never stall a concurrent predict_batch or another tap thread
            mu, var = gp.predict(X, return_var=True)
            z = float(np.mean(np.abs(y - mu) / np.sqrt(var)))
        with self._lock:
            if self.frozen:
                # re-checked under the lock: a wave in flight when
                # freeze() lands must not be absorbed after it
                return
            if z is not None:
                a = self.ewma_alpha
                self.err_ewma = (
                    z if self.err_ewma is None else (1 - a) * self.err_ewma + a * z
                )
                if self.err_ewma > self.stale_z:
                    self._hyper_stale = True
            if self._X is None:
                self._X, self._y = X.copy(), y.copy()
            else:
                self._X = np.concatenate([self._X, X])[-self.window:]
                self._y = np.concatenate([self._y, y])[-self.window:]
            self.n_seen += len(y)
            self._since_refit += len(y)

    def _current_fit(self) -> GP:
        """The up-to-date fit, refitting lazily first. The expensive part
        (Cholesky / Type-II MLE) runs OUTSIDE the lock so the fabric
        collector thread can keep streaming `add()` traffic meanwhile;
        concurrent predictors may duplicate a refit (last writer wins),
        which costs work but never correctness — in practice one sampler
        thread predicts."""
        with self._lock:
            if len(self) < self.min_train:
                raise RuntimeError(
                    f"OnlineGP not ready: window holds {len(self)} < "
                    f"min_train={self.min_train} points"
                )
            fresh = self._gp is not None and self._since_refit < self.refit_every
            if fresh and not self._hyper_stale:
                return self._gp
            X, y = self._X.copy(), self._y.copy()
            hyper = self._hyper_stale or self._gp is None
            params = None if hyper else self._gp.log_params
            absorbed = self._since_refit
        gp = (
            GP.fit(X, y, n_iters=self.hyper_iters, seed=self.seed)
            if params is None
            else GP.from_params(X, y, params)
        )
        with self._lock:
            self._gp = gp
            if params is None:
                self.n_hyper_fits += 1
                self._hyper_stale = False
                self.err_ewma = None  # fresh hyperparameters reset the probe
            else:
                self.n_chol_refits += 1
            # points streamed in DURING the fit stay pending for the next one
            self._since_refit = max(0, self._since_refit - absorbed)
        return gp

    def predict_batch(self, Xq: np.ndarray, return_var: bool = False):
        """[Q, d] -> mu [Q] (and var [Q], strictly positive) in ONE batched
        linear-algebra call — zero model waves. Lazily refits first."""
        return self._current_fit().predict(Xq, return_var=return_var)

    def stats(self) -> dict:
        with self._lock:
            return {
                "n": len(self),
                "window": self.window,
                "n_seen": self.n_seen,
                "hyper_fits": self.n_hyper_fits,
                "chol_refits": self.n_chol_refits,
                "err_ewma": None if self.err_ewma is None else round(self.err_ewma, 3),
                "ready": self._gp is not None or len(self) >= self.min_train,
                "frozen": self.frozen,
            }

    # -- campaign checkpointing ---------------------------------------------
    def snapshot(self) -> dict:
        """Consistent copy of the learnable state — the training window plus
        the staleness/counter bookkeeping — for `CampaignCheckpoint`. Arrays
        come out as arrays (checkpoint leaves); scalars are JSON-able. The
        fit itself is NOT captured: `restore` marks it dirty and the first
        `predict_batch` after resume re-factorizes the restored window."""
        with self._lock:
            return {
                "X": None if self._X is None else self._X.copy(),
                "y": None if self._y is None else self._y.copy(),
                "n_seen": self.n_seen,
                "since_refit": self._since_refit,
                "err_ewma": self.err_ewma,
                "frozen": self.frozen,
            }

    def restore(self, snap: dict) -> None:
        """Re-apply a `snapshot()` — the window is restored verbatim and the
        factorization is rebuilt lazily (hyperparameter search included, so
        a resumed screen trains from exactly the data it had)."""
        with self._lock:
            X, y = snap.get("X"), snap.get("y")
            self._X = None if X is None else np.atleast_2d(np.asarray(X, float)).copy()
            self._y = None if y is None else np.asarray(y, float).ravel().copy()
            self.n_seen = int(snap.get("n_seen", 0))
            self._since_refit = int(snap.get("since_refit", 0))
            e = snap.get("err_ewma")
            self.err_ewma = None if e is None else float(e)
            self.frozen = bool(snap.get("frozen", False))
            self._gp = None
            self._hyper_stale = True
