"""Plain reference of one coded stochastic incremental ADMM run (csI-ADMM).

One run of arXiv 2010.00914 Algorithm 2 on decentralized least squares
(eq. 24), written out iteration by iteration in numpy, followed by the
streaming summaries a sweep reports for it. It imports nothing of the
program under test and takes nothing the program made: the data, the
token route, the ECN and link times and the gradient codes are drawn here
from the run's seed, on the seed streams the deployment documents
(data and route on ``seed``, times on ``seed + 1``, codes on ``seed``).

Per iteration k, with active agent i = route[k mod N]:

- each of the K partitions of agent i gives the mean gradient of its
  mu-row mini-batch at offset ((k // N) mod floor(P / mu)) * mu;
- ECN j sends the coded message m_j = sum_t B[j, t] g_t; the agent
  decodes G = (1/K) sum_{j alive} a_j m_j with a^T B[alive] = 1^T solved
  by least squares (eq. 6; exactly for exact codes, within the certified
  bound for the partial-recovery family);
- eqs. (5a), (5b), (4c) update x_i, y_i and z;
- accuracy (eq. 23, x_init = 0), the test mean-square error of z and the
  clock (the R-th response, capped at epsilon or cut at the deadline,
  plus one token hop) are recorded.

``dtype="bfloat16"`` rounds every stored intermediate to bfloat16: the
control that the comparison has to reject.
"""

from __future__ import annotations

import itertools
import math

import numpy as np

__all__ = ["make_code", "run", "summarize", "at_budget", "time_to", "quantiles"]


def _rounder(dtype: str):
    if dtype == "float64":
        return lambda a: np.asarray(a, np.float64)
    import ml_dtypes

    low = np.dtype(getattr(ml_dtypes, dtype, None) or dtype)
    return lambda a: np.asarray(a, np.float64).astype(low).astype(np.float64)


# -- data and fleet draws ---------------------------------------------------


def dataset(spec: dict, seed: int):
    """Planted linear model: x_o, O ~ N(0, I), T = O x_o + noise * e."""
    n_train, n_test = spec["n_train"], spec["n_test"]
    p, d = spec["p"], spec["d"]
    rng = np.random.default_rng(seed)
    x_o = rng.standard_normal((p, d))
    O = rng.standard_normal((n_train + n_test, p))
    T = O @ x_o + spec["noise"] * rng.standard_normal((n_train + n_test, d))
    return O[:n_train], T[:n_train], O[n_train:], T[n_train:]


def ecn_and_link_times(timing: dict, case: dict, iters: int, K: int):
    """(iters, K) ECN response times and (iters,) token-hop times."""
    rng = np.random.default_rng(case["seed"] + 1)
    lo, hi = timing["base_lo"], timing["base_hi"]
    response = case["response"]
    if response == "uniform":
        base = rng.uniform(lo, hi, size=(iters, K))
    elif response == "shifted_exp":
        base = lo + rng.exponential(hi - lo, size=(iters, K))
    elif response == "lognormal":
        base = lo + (hi - lo) * rng.lognormal(-0.5, 1.0, size=(iters, K))
    elif response == "pareto":
        base = lo + (hi - lo) * rng.pareto(2.0, size=(iters, K))
    else:
        raise ValueError(f"unknown response model {response!r}")
    straggle = rng.random((iters, K)) < case["p_straggle"]
    extra = rng.exponential(case["delay"], size=(iters, K))
    speed = np.resize(np.asarray(case["speed_classes"], float), K)
    ecn = base * speed[None, :] + straggle * extra
    link = rng.uniform(timing["comm_lo"], timing["comm_hi"], size=iters)
    return ecn, link


# -- gradient codes ---------------------------------------------------------


def _lstsq_decode(B: np.ndarray, alive: np.ndarray):
    idx = np.nonzero(alive)[0]
    ones = np.ones(B.shape[1])
    a_idx = np.linalg.lstsq(B[idx].T, ones, rcond=None)[0]
    a = np.zeros(B.shape[0])
    a[idx] = a_idx
    return a, float(np.linalg.norm(B[idx].T @ a_idx - ones))


def _dead_patterns(K: int, n_dead: int):
    for dead in itertools.combinations(range(K), n_dead):
        alive = np.ones(K, dtype=bool)
        alive[list(dead)] = False
        yield alive


def _cyclic_B(K: int, S: int, seed: int) -> np.ndarray:
    """Tandon et al.'s randomized cyclic code: ECN j holds partitions
    j..j+S (mod K); rows read off null(H) with H 1 = 0, redrawn until
    every S-straggler pattern decodes exactly."""
    rng = np.random.default_rng(seed)
    for _ in range(16):
        H = rng.standard_normal((S, K))
        H[:, -1] -= H.sum(axis=1)
        B = np.zeros((K, K))
        ok = True
        for j in range(K):
            cols = (j + np.arange(S + 1)) % K
            _, sv, Vt = np.linalg.svd(H[:, cols])
            coef = Vt[-1]
            if (S > 0 and sv[-1] < 1e-10) or abs(coef.sum()) < 1e-10:
                ok = False
                break
            B[j, cols] = coef * ((S + 1) / coef.sum())
        if ok and all(
            _lstsq_decode(B, a)[1] <= 1e-6 for a in _dead_patterns(K, S)
        ):
            return B
    raise RuntimeError(f"no decodable cyclic code for K={K}, S={S}")


def make_code(scheme: str, K: int, S: int, seed: int) -> dict:
    """Encode matrix B, responses R, fewest responses decoded, and the
    residual that decode accepts."""
    R = K - S
    if scheme == "uncoded" or (scheme == "cyclic" and S == 0):
        return dict(B=np.eye(K), R=K, r_min=K, tol=1e-6)
    if scheme == "cyclic":
        return dict(B=_cyclic_B(K, S, seed), R=R, r_min=R, tol=1e-6)
    if scheme == "mds":
        nodes = np.cos((2 * np.arange(K) + 1) * np.pi / (2 * K))
        W = np.vander(nodes, R, increasing=True)
        rng = np.random.default_rng(seed)
        basis = np.concatenate(
            [np.ones((K, 1)) / np.sqrt(K), rng.standard_normal((K, R - 1))],
            axis=1,
        )
        return dict(B=W @ np.linalg.qr(basis)[0].T, R=R, r_min=R, tol=1e-6)
    if scheme == "approx":
        B = _cyclic_B(K, S, seed)
        r_min = max(1, K - 2 * S)
        if math.comb(K, K - r_min) > 4096:
            bound = float(np.sqrt(K))
        else:
            bound = max(
                _lstsq_decode(B, a)[1] for a in _dead_patterns(K, K - r_min)
            )
        return dict(B=B, R=R, r_min=r_min, tol=bound * (1 + 1e-6) + 1e-9)
    raise ValueError(f"unknown code family {scheme!r}")


def decode_schedule(ecn: np.ndarray, case: dict, code: dict):
    """Per iteration: decode vector over ECNs (zeros when undecodable)
    and the agent's wait."""
    iters, K = ecn.shape
    eps = case["epsilon"]
    dl = case.get("deadline")
    decode = np.zeros((iters, K))
    wait = np.zeros(iters)
    cache: dict = {}
    for k in range(iters):
        t = ecn[k]
        if case["scheme"] == "uncoded":
            alive = t <= eps
            if alive.any():
                wait[k] = min(t.max(), eps)
            else:
                alive[np.argmin(t)] = True
                wait[k] = t.min()
            decode[k] = alive * (K / alive.sum())
            continue
        order = np.argsort(t)
        alive = np.zeros(K, dtype=bool)
        alive[order[: code["R"]]] = True
        wait[k] = min(t[order[code["R"] - 1]], eps)
        if dl is not None and code["r_min"] < code["R"]:
            arrived = t <= dl
            n = int(arrived.sum())
            if code["r_min"] <= n < code["R"] and dl < wait[k]:
                alive, wait[k] = arrived, dl
        key = alive.tobytes()
        if key not in cache:
            a, resid = _lstsq_decode(code["B"], alive)
            cache[key] = a if resid <= code["tol"] else None
        if cache[key] is None:
            wait[k] = eps
        else:
            decode[k] = cache[key]
    return decode, wait


# -- the run ---------------------------------------------------------------


def run(config: dict, case: dict, iters: int, dtype: str = "float64") -> dict:
    """Per-iteration accuracy, test error and clocks of one run."""
    q = _rounder(dtype)
    N, K = case["N"], case["K"]
    S, M = case["S"], case["M"]
    O_tr, T_tr, O_te, T_te = dataset(config["dataset"], case["seed"])
    b = (O_tr.shape[0] // N // K) * K
    p, d = O_tr.shape[1], T_tr.shape[1]
    O = q(O_tr[: N * b].reshape(N, b, p))
    T = q(T_tr[: N * b].reshape(N, b, d))
    O_te, T_te = q(O_te), q(T_te)

    # Closed-form optimum of sum_i f_i (eq. 1), in float64.
    H = sum(O_tr[i * b:(i + 1) * b].T @ O_tr[i * b:(i + 1) * b] for i in range(N))
    g = sum(O_tr[i * b:(i + 1) * b].T @ T_tr[i * b:(i + 1) * b] for i in range(N))
    x_star = q(np.linalg.solve(H / b, g / b))
    xs_norm = max(float(np.linalg.norm(x_star)), 1e-12)

    route = np.random.default_rng(case["seed"]).permutation(N)
    P = b // K
    mu = (M // (S + 1)) // K
    nb = max(P // mu, 1)

    code = make_code(case["scheme"], K, S, case["seed"])
    ecn, link = ecn_and_link_times(config["timing"], case, iters, K)
    decode, wait = decode_schedule(ecn, case, code)

    rho, c_tau, c_gamma = case["rho"], case["c_tau"], case["c_gamma"]
    x = np.zeros((N, p, d))
    y = np.zeros((N, p, d))
    z = np.zeros((p, d))
    acc = np.zeros(iters)
    test = np.zeros(iters)
    for k in range(iters):
        i = route[k % N]
        off = ((k // N) % nb) * mu
        grads = np.zeros((K, p * d))
        for t in range(K):
            Ob = O[i, t * P + off: t * P + off + mu]
            Tb = T[i, t * P + off: t * P + off + mu]
            r = q(q(Ob @ x[i]) - Tb)
            grads[t] = q(q(Ob.T @ r) / mu).ravel()
        a = decode[k]
        msgs = q(code["B"] @ grads)  # coded messages of all K ECNs
        G = q(q(a @ msgs) / K).reshape(p, d)  # dead ECNs carry a_j = 0
        tau = q(c_tau * math.sqrt(k + 1))
        gamma = q(c_gamma / math.sqrt(k + 1))
        num = q(q(q(q(tau * x[i]) + q(rho * z)) + y[i]) - G)
        x_new = q(num / q(rho + tau))
        y_new = q(y[i] + q(rho * gamma * q(z - x_new)))
        z = q(z + q(q(q(x_new - x[i]) - q(q(y_new - y[i]) / rho)) / N))
        x[i], y[i] = x_new, y_new
        err = np.linalg.norm((x - x_star[None]).reshape(N, -1), axis=1)
        acc[k] = q(np.mean(q(err / xs_norm)))
        resid = q(q(O_te @ z) - T_te)
        test[k] = q(np.mean(np.sum(q(resid * resid), axis=-1)))

    clock = np.zeros(iters)
    now = 0.0
    for k in range(iters):  # cumulative, rounded as it accumulates
        now = float(q(now + q(wait[k] + link[k])))
        clock[k] = now
    return dict(
        accuracy=acc,
        test_error=test,
        sim_time=clock,
        comm_cost=q(np.arange(1, iters + 1, dtype=np.float64)),
        test_scale=float(np.mean(np.sum(T_te * T_te, axis=-1))),
    )


# -- streaming summaries ----------------------------------------------------


def at_budget(ys, x, budgets):
    idx = np.searchsorted(x, np.asarray(budgets, float), "right") - 1
    return ys[np.clip(idx, 0, len(ys) - 1)]


def time_to(ys, x, targets):
    out = np.full(len(targets), np.inf)
    for j, tg in enumerate(targets):
        hit = np.nonzero(ys <= tg)[0]
        if len(hit):
            out[j] = x[hit[0]]
    return out


def quantiles(ys, red):
    lo, hi, bins = red["lo"], red["hi"], red["bins"]
    b = np.clip(np.floor((ys - lo) / (hi - lo) * bins), 0, bins - 1).astype(int)
    cdf = np.cumsum(np.bincount(b, minlength=bins).astype(np.float64))
    idx = np.clip(
        np.searchsorted(cdf, np.asarray(red["quantiles"], float) * len(ys)),
        0, bins - 1,
    )
    return lo + (idx + 0.5) * (hi - lo) / bins


def summarize(tr: dict, red: dict) -> dict:
    """The sweep's summaries of one run: clock finals, and per field the
    final, mean, variance (n - 1), minimum, value at each budget of the
    clock, clock at which each target is first reached (inf if never)
    and histogram quantiles."""
    x = tr[red["x"]]
    out = {"sim_time/final": tr["sim_time"][-1], "comm_cost/final": tr["comm_cost"][-1]}
    for f in red["fields"]:
        ys = tr[f]
        out[f"{f}/final"] = ys[-1]
        out[f"{f}/mean"] = ys.mean()
        out[f"{f}/var"] = ys.var(ddof=1)
        out[f"{f}/min"] = ys.min()
        if red.get("budgets"):
            out[f"{f}/at_budget"] = at_budget(ys, x, red["budgets"])
        if red.get("targets"):
            out[f"{f}/time_to"] = time_to(ys, x, red["targets"])
        if red.get("quantiles"):
            out[f"{f}/quantiles"] = quantiles(ys, red)
    return {k: np.asarray(v, np.float64) for k, v in out.items()}
