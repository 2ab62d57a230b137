"""Seeded inputs: matrices made on the device, and the open-loop schedule.

Everything here is the benchmark's own copy, so a change to the program
cannot change what is measured. `make_spd` follows the SPD family the
program's tests use (B Bᵀ/n + I with B standard normal, formed at HIGHEST
precision so a seed gives the same matrix on every backend).
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

# Device streams split off one seed; a new consumer takes a new number.
STREAM_MATRIX = 0          # + index of the matrix or tenant
STREAM_PANELS = 1000
STREAM_FACTORS = 1001


def seed_words(seed: int) -> tuple[int, int]:
    """Two 32-bit words from a seed of any size (seeds may exceed 2**32)."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    w = np.random.SeedSequence(seed).generate_state(2)
    return int(w[0]), int(w[1])


def device_key(seed: int, stream: int):
    """A JAX PRNG key for one stream of one seed."""
    import jax

    w0, w1 = seed_words(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(w0), w1)
    return jax.random.fold_in(key, stream)


def make_spd(n: int, key):
    """B Bᵀ/n + I, B standard normal (n, n), in f32 at HIGHEST precision.

    Its spectrum lies in about [1, 5] (Marchenko–Pastur at ratio 1, shifted
    by one), so the condition number is about 5.
    """
    import jax
    import jax.numpy as jnp

    b = jax.random.normal(key, (n, n), dtype=jnp.float32)
    return (jnp.matmul(b, b.T, precision=jax.lax.Precision.HIGHEST) / n
            + jnp.eye(n, dtype=jnp.float32))


@functools.lru_cache(maxsize=None)
def _spd_program(sharding):
    import jax

    return jax.jit(make_spd, static_argnums=0, out_shardings=sharding)


def spd_matrix(n: int, seed: int, index: int, sharding=None):
    """Matrix `index` of `seed`, made on the device by one jitted call
    (already laid out by `sharding` when one is given)."""
    return _spd_program(sharding)(n, device_key(seed, STREAM_MATRIX + index))


def panel_pool(n: int, count: int, cols: int, seed: int):
    """(count, n, cols) standard-normal right-hand sides, one jitted call."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda k: jax.random.normal(k, (count, n, cols),
                                             jnp.float32))
    return fn(device_key(seed, STREAM_PANELS))


def factor_pool(n: int, count: int, rank: int, seed: int):
    """(count, n, rank) update factors u/√n: A + u uᵀ stays SPD, and each
    update moves `rank` eigenvalues by about 1."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda k: jax.random.normal(k, (count, n, rank),
                                             jnp.float32) / np.sqrt(n))
    return fn(device_key(seed, STREAM_FACTORS))


# ---------------------------------------------------------------------------
# The open-loop request schedule (YCSB-style mixes)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Request:
    """One scheduled request: due `due` seconds after the window opens."""

    due: float
    op: str            # "solve" | "update"
    tenant: int
    item: int          # index into the panel pool (solve) or factor pool
    check: bool        # solve answers kept for the comparison


def zipf_shares(count: int, theta: float) -> np.ndarray:
    """YCSB's zipfian request distribution over `count` keys."""
    w = 1.0 / np.arange(1, count + 1) ** theta
    return w / w.sum()


def _apportion(total: int, shares: np.ndarray) -> np.ndarray:
    """Largest-remainder rounding: integer counts summing to `total`."""
    raw = shares * total
    counts = np.floor(raw).astype(int)
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[: total - counts.sum()]] += 1
    return counts


def open_loop_schedule(mix: dict, seconds: float, seed: int
                       ) -> list[Request]:
    """The requests due in a window of `seconds` at the mix's rate.

    `rate_per_s` is the mean rate over the window. With `on_s` and `off_s`
    in the mix, requests arrive in bursts: only during phases of `on_s`
    seconds, each followed by `off_s` seconds of silence, at the rate that
    keeps that mean. Without them arrivals never pause.

    Every seed gets the same work in another order: the same number of
    requests, the same count of each (operation, tenant) pair, the same
    set of inter-arrival gaps (the exponential distribution's quantiles at
    evenly spaced probabilities, so arrivals are Poisson-like), and the
    same number of checked answers. The seed shuffles the order and picks
    pool items, so runs with different seeds do the same amount of work.
    """
    rate = float(mix["rate_per_s"])
    total = max(1, int(round(rate * seconds)))
    on = float(mix.get("on_s", seconds))
    off = float(mix.get("off_s", 0.0))
    rng = np.random.default_rng(seed)
    q = (np.arange(total) + 0.5) / total
    gaps = rng.permutation(-np.log1p(-q) / (rate * (on + off) / on))
    due_on = np.cumsum(gaps)            # time counted in on-phases only
    due = due_on + np.floor(due_on / on) * off

    tenants = int(mix["tenants"])
    shares = zipf_shares(tenants, float(mix["zipf_theta"]))
    updates = int(round(total * float(mix["update_share"])))
    ops = []
    for op, count in (("update", updates), ("solve", total - updates)):
        for tenant, k in enumerate(_apportion(count, shares)):
            ops += [(op, tenant)] * int(k)
    ops = [ops[i] for i in rng.permutation(total)]
    solves = [i for i, (op, _) in enumerate(ops) if op == "solve"]
    n_check = int(round(len(solves) * float(mix["check_share"])))
    checked = set(rng.choice(solves, size=n_check, replace=False).tolist()
                  if n_check else [])
    items = rng.integers(0, 1 << 30, size=total)
    out = []
    for i, (op, tenant) in enumerate(ops):
        pool = mix["panel_pool"] if op == "solve" else mix["factor_pool"]
        out.append(Request(due=float(due[i]), op=op, tenant=tenant,
                           item=int(items[i] % pool), check=i in checked))
    return out
