"""The expert layer's row passes (`ops/pallas/moe_rows.py`), interpreted on
the CPU, against the formulas they replace in `parallel/moe.py`: the
gathers equal to the bit, the sums to float32 rounding (1e-6 of a norm;
the limit 1e-5 stands far under a row missed or counted twice, order
1e-1).

Five buffers, each a case of every test: no live row; every row live (every
choice held, `T k` a whole number of tiles); groups whose edges fall inside
a tile; the last again with every row the kernels must not read set to
NaN (the buffer's dead rows, the tokens no held pair names), where the
result must stay finite and equal; and every choice computed by the held
expert in its place (`held_experts`' `stand_in`), so that a held expert
holds up to three pairs of one token."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deep_vision_tpu.ops.pallas import moe_rows

T, K, E, HELD, D, F = 64, 4, 12, 4, 256, 128
R = -(-T * K // moe_rows.TILE) * moe_rows.TILE
TOL = 1e-5
CASES = ["no_live_row", "every_row_live", "edges_inside_tiles",
         "nan_in_dead_rows", "copies_of_the_held"]


def apart(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def routing(case):
    """Every token's K distinct choices of E experts, the first HELD held,
    sorted into the buffer as `held_experts` sorts them."""
    rs = np.random.RandomState(CASES.index(case))
    if case == "no_live_row":
        choice = np.stack([HELD + rs.permutation(E - HELD)[:K]
                           for _ in range(T)])
    elif case == "every_row_live":
        choice = np.stack([rs.permutation(HELD)[:K] for _ in range(T)])
    elif case == "copies_of_the_held":
        # half the tokens choose the three copies of one held expert
        choice = np.stack([
            np.r_[np.arange(rs.randint(HELD), E, HELD),
                  rs.randint(HELD)][:K] if t % 2 else rs.permutation(E)[:K]
            for t in range(T)])
        choice[1::2, -1] = (choice[1::2, 0] + 1) % HELD
    else:
        choice = np.stack([rs.permutation(E)[:K] for _ in range(T)])
    local = choice.reshape(-1)
    if case == "copies_of_the_held":
        local = local % HELD
    held = local < HELD
    key = np.where(held, local, HELD)
    order = np.pad(np.argsort(key, kind="stable"), (0, R - T * K))
    sizes = np.bincount(key, minlength=HELD + 1)
    tt = moe_rows.token_tile(T)
    per = np.eye(HELD + 1, dtype=np.int64)[key].reshape(
        T // tt, tt * K, HELD + 1)[..., :HELD].sum(1)
    lo = np.cumsum(sizes[:HELD]) - sizes[:HELD] + np.cumsum(per, 0) - per
    inv = np.empty(T * K, np.int64)
    inv[order[:T * K]] = np.arange(T * K)
    live = int(sizes[:HELD].sum())
    assert (live == 0) == (case == "no_live_row")
    assert (live == T * K == R) == (case in ("every_row_live",
                                             "copies_of_the_held"))
    # the most pairs of one token in one held expert
    held_of_token = np.eye(HELD + 1, dtype=int)[key].reshape(T, K, -1)
    assert np.max(held_of_token.sum(1)[:, :HELD]) == (
        3 if case == "copies_of_the_held" else int(live > 0))
    return {"order": jnp.asarray(order, jnp.int32), "live": live,
            "lo": jnp.asarray(lo, jnp.int32),
            "hi": jnp.asarray(lo + per, jnp.int32),
            "held": held.reshape(T, K), "inv": inv.reshape(T, K)}


def data(case, shape, seed, dead=None):
    """Seeded normal numbers; where `dead` (a row mask) and the case asks,
    NaN."""
    x = np.random.RandomState(seed).randn(*shape).astype(np.float32)
    if case == "nan_in_dead_rows" and dead is not None:
        x[dead] = np.nan
    return x


def live_tiles(live):
    return -(-live // moe_rows.TILE) * moe_rows.TILE


def buffer(case, r):
    return data(case, (R, D), 1, dead=np.arange(R) >= r["live"])


def rows_of(buf, r):
    """(T, K, D) each pair's buffer row, zeros where the pair is not held."""
    got = buf[np.where(r["held"], r["inv"], 0)]
    return np.where(r["held"][..., None], got, 0.0)


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("scaled", [False, True], ids=["plain", "scaled"])
def test_gather_rows_are_the_indexed_rows(case, scaled):
    """Row `p < live` is `src[idx[p]]` (times `scale[p]` in float32, then
    rounded to bf16, as the combine's backward did), the rest of its tile
    zeros; a source row no live row names is never used."""
    r = routing(case)
    order = np.asarray(r["order"])
    named = np.isin(np.arange(T), order[:r["live"]] // K)
    src = jnp.asarray(data(case, (T, D), 2, dead=~named), jnp.bfloat16)
    idx = r["order"] // K
    scale = jnp.asarray(np.random.RandomState(3).rand(R), jnp.float32)
    got = moe_rows.gather_rows(src, idx, r["live"],
                               scale if scaled else None)
    want = src[idx]
    if scaled:
        want = (want.astype(jnp.float32) * scale[:, None]).astype(src.dtype)
    live, end = r["live"], live_tiles(r["live"])
    assert got.dtype == src.dtype
    np.testing.assert_array_equal(np.asarray(got[:live], np.float32),
                                  np.asarray(want[:live], np.float32))
    assert not np.any(np.asarray(got[live:end], np.float32))


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("weighted", [True, False],
                         ids=["weighted", "plain"])
def test_sum_rows_is_each_tokens_weighted_sum(case, weighted):
    """Token `t` is `sum_j w[t, j] buf[row of (t, j)]` over its held pairs,
    in float32: the combine (weighted, float32) and the dispatch's
    transpose (ones, in the buffer's dtype); zeros where none is held."""
    r = routing(case)
    buf = buffer(case, r)
    w = np.random.RandomState(4).rand(T, K).astype(np.float32)
    by_row = jnp.asarray(w.reshape(-1), jnp.float32)[r["order"]]
    dtype = jnp.float32 if weighted else jnp.bfloat16
    got = moe_rows.sum_rows(jnp.asarray(buf), r["order"], r["lo"], r["hi"],
                            by_row if weighted else None, tokens=T, k=K,
                            dtype=dtype)
    want = np.einsum("tkd,tk->td", rows_of(buf, r),
                     w if weighted else np.ones_like(w))
    assert got.dtype == dtype and np.all(np.isfinite(np.asarray(got)))
    if weighted:
        assert apart(got, want) < TOL
    else:  # one rounding to bf16 of the float32 sum
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(jnp.asarray(want, jnp.float32).astype(dtype),
                       np.float32))


@pytest.mark.parametrize("case", CASES)
def test_dot_rows_is_each_pairs_row_against_its_tokens_cotangent(case):
    """`[t, j]` is `<buf[row of (t, j)], g[t]>` in float32 where the pair
    is held, zero where not: the routing weights' gradient."""
    r = routing(case)
    buf = buffer(case, r)
    g = data(case, (T, D), 5)
    got = moe_rows.dot_rows(jnp.asarray(buf), r["order"], r["lo"], r["hi"],
                            jnp.asarray(g), k=K)
    want = np.einsum("tkd,td->tk", rows_of(buf, r), g)
    assert np.all(np.isfinite(np.asarray(got)))
    assert not np.any(np.asarray(got)[~r["held"]])
    assert apart(got, want) < TOL


@pytest.mark.parametrize("case", CASES)
def test_gate_rows_and_their_gradient_are_silu_times_up(case):
    """`silu(gate) * up` over the live rows and its gradient to both
    halves, the rows past `live` of the last live tile zeros."""
    r = routing(case)
    live, end = r["live"], live_tiles(r["live"])
    dead = np.arange(R) >= live
    gu = data(case, (R, 2 * F), 6, dead=dead)
    dh = data(case, (R, F), 7, dead=dead)
    clean = jnp.asarray(np.where(dead[:, None], 0.0, gu))

    def formula(a):
        return jax.nn.silu(a[:, :F]) * a[:, F:]

    got, vjp = jax.vjp(lambda a: moe_rows.gate_rows(a, live),
                       jnp.asarray(gu))
    assert apart(got[:live], formula(clean)[:live]) < TOL
    assert not np.any(np.asarray(got[live:end]))
    (grad,) = vjp(jnp.asarray(dh))
    _, formula_vjp = jax.vjp(formula, clean)
    (want,) = formula_vjp(jnp.asarray(np.where(dead[:, None], 0.0, dh)))
    assert apart(grad[:live], want[:live]) < TOL
    assert not np.any(np.asarray(grad[live:end]))


def test_the_token_tile_divides_the_tokens():
    assert [moe_rows.token_tile(t) for t in (4096, 192, 40, 24, 16)] == [
        128, 64, 8, 8, 16]
    with pytest.raises(ValueError, match="multiple of 8"):
        moe_rows.token_tile(20)
