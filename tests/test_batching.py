"""Pad-and-bucket planner properties (DESIGN.md §19).

The planner is pure bookkeeping — no jax — so its contracts are tested
as properties over randomized instance populations: exact partition
(every instance lands in exactly one bucket), bounded padding waste,
deterministic keys (stable across orderings and processes), and the
end-to-end guarantee the waste bound exists to protect: a padded
instance's trajectory is bit-identical to its unpadded single solve.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given
from hypothesis import strategies as st

from repro.core.batching import (BatchAxes, OpenBucketPlanner, bucket_key,
                                 instance_records, pad_tree_records,
                                 plan_buckets, stack_trees,
                                 static_signature)

AX = BatchAxes(record_axes=(0, 0))


def _population(n, seed, shapes=((16, 16), (20, 20))):
    """n two-array instances with mixed trailing shapes + record counts."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        S = shapes[int(rng.integers(len(shapes)))]
        rec = int(rng.integers(1, 7))
        out.append((np.zeros((rec,) + S, np.float32),
                    np.zeros((rec,) + S, np.float32)))
    return out


# ---------------------------------------------------------------------
# Partition / waste / determinism properties
# ---------------------------------------------------------------------

@given(n=st.integers(1, 24), seed=st.integers(0, 3))
def test_every_instance_in_exactly_one_bucket(n, seed):
    insts = _population(n, seed)
    buckets = plan_buckets(insts, AX)
    covered = [i for b in buckets for i in b.indices]
    assert sorted(covered) == list(range(n))        # exact partition


@given(n=st.integers(1, 24), seed=st.integers(0, 3))
def test_padding_within_waste_budget(n, seed):
    insts = _population(n, seed)
    for budget in (0.0, 0.25, 0.5):
        buckets = plan_buckets(insts, AX, waste_budget=budget)
        for b in buckets:
            slack = sum(b.capacity - r for r in b.records)
            assert b.capacity == max(b.records)
            assert slack <= budget * b.capacity * len(b.indices)
            # members agree on the static signature by construction
            sigs = {static_signature(insts[i], AX) for i in b.indices}
            assert len(sigs) == 1


@given(n=st.integers(2, 16), seed=st.integers(0, 2))
def test_bucket_keys_deterministic_and_order_free(n, seed):
    insts = _population(n, seed)
    a = plan_buckets(insts, AX, salt="s")
    b = plan_buckets(list(insts), AX, salt="s")
    assert [x.key for x in a] == [x.key for x in b]
    # the key binds the salt (problem + config fingerprint)
    c = plan_buckets(insts, AX, salt="other")
    assert {x.key for x in a}.isdisjoint({x.key for x in c})
    # keys are content-addressed, reproducible from the parts
    for x in a:
        members = list(zip(x.indices, x.records))
        assert all(instance_records(insts[i], AX) == r
                   for i, r in members)
        assert x.key == bucket_key("s", x.signature, x.capacity, members)


def test_zero_waste_budget_buckets_by_exact_records():
    insts = _population(12, 0)
    for b in plan_buckets(insts, AX, waste_budget=0.0):
        assert len(set(b.records)) == 1              # no padding at all


def test_no_pad_records_mode_never_mixes_record_counts():
    ax = BatchAxes(record_axes=(1, 1), pad_records=False)
    rng = np.random.default_rng(1)
    insts = [(np.zeros((5, int(k)), np.float32),
              np.zeros((3, int(k)), np.float32))
             for k in rng.integers(4, 8, size=10)]
    for b in plan_buckets(insts, ax):
        assert len(set(b.records)) == 1
        assert b.capacity == b.records[0]


def test_waste_budget_validation():
    insts = _population(2, 0)
    with pytest.raises(ValueError, match="waste_budget"):
        plan_buckets(insts, AX, waste_budget=1.0)
    with pytest.raises(ValueError, match="waste_budget"):
        plan_buckets(insts, AX, waste_budget=-0.1)


def test_pad_tree_records_contract():
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(3, 2)}
    padded = pad_tree_records(tree, 5)
    assert padded["a"].shape == (5, 2)
    np.testing.assert_array_equal(np.asarray(padded["a"][3:]), 0.0)
    np.testing.assert_array_equal(np.asarray(padded["a"][:3]),
                                  np.asarray(tree["a"]))
    with pytest.raises(ValueError):
        pad_tree_records(tree, 2)
    stacked = stack_trees([padded, padded])
    assert stacked["a"].shape == (2, 5, 2)


def _inst(rec, S=16):
    return (np.zeros((rec, S, S), np.float32),
            np.zeros((rec, S, S), np.float32))


def test_bucket_key_stable_under_member_permutation():
    """The key pins *membership*, not arrival order: any permutation of
    the (index, records) list hashes identically, and any change to the
    membership, capacity or salt does not."""
    members = [(0, 5), (1, 3), (2, 5), (3, 1)]
    sig = static_signature(_inst(5), AX)
    want = bucket_key("s", sig, 5, members)
    for perm in ([members[i] for i in (2, 0, 3, 1)],
                 list(reversed(members)),
                 [members[i] for i in (1, 3, 0, 2)]):
        assert bucket_key("s", sig, 5, perm) == want
    assert bucket_key("s", sig, 5, members[:-1]) != want
    assert bucket_key("s", sig, 6, members) != want
    assert bucket_key("t", sig, 5, members) != want


def test_waste_budget_exact_boundary():
    """The admission rule is ``pad <= budget * cap * n`` — exactly at
    the budget admits, one record over splits.  budget=0.1, cap 10:
    records {10, 8} pad 2 == 0.1*10*2 -> one bucket; {10, 7} pad 3 ->
    two."""
    at = plan_buckets([_inst(10), _inst(8)], AX, waste_budget=0.1)
    assert len(at) == 1 and at[0].capacity == 10
    over = plan_buckets([_inst(10), _inst(7)], AX, waste_budget=0.1)
    assert len(over) == 2
    assert sorted(b.capacity for b in over) == [7, 10]


# ---------------------------------------------------------------------
# Incremental (open-bucket) planning — the serving scheduler's half
# ---------------------------------------------------------------------

def test_open_bucket_waste_boundary_matches_offline():
    """Arrival-order admission enforces the identical boundary: small
    then large grows the capacity and re-checks the rule."""
    p = OpenBucketPlanner(AX, waste_budget=0.1)
    b1 = p.offer("a", _inst(8))
    assert p.offer("b", _inst(10)) is b1      # pad 2 == 0.1*10*2
    assert b1.capacity == 10                  # grew to largest member
    p2 = OpenBucketPlanner(AX, waste_budget=0.1)
    b2 = p2.offer("a", _inst(7))
    assert p2.offer("b", _inst(10)) is not b2  # pad 3 > 2: new bucket
    assert len(p2.open_buckets) == 2


def test_open_bucket_planner_keys_match_offline_planner():
    """A closed open-bucket's key is the one ``plan_buckets`` emits for
    the same membership — checkpoints written by a served batch resume
    under the offline planner and vice versa."""
    insts = [_inst(5), _inst(5), _inst(4)]
    offline = plan_buckets(insts, AX, waste_budget=0.25, salt="s")
    assert len(offline) == 1
    p = OpenBucketPlanner(AX, waste_budget=0.25, salt="s")
    buckets = {id(p.offer(i, inst)) for i, inst in enumerate(insts)}
    assert len(buckets) == 1
    closed = p.drain()
    assert [b.key for b in closed] == [offline[0].key]
    # ... and the key is arrival-order independent
    p2 = OpenBucketPlanner(AX, waste_budget=0.25, salt="s")
    for i in (2, 0, 1):
        p2.offer(i, insts[i])
    assert p2.drain()[0].key == offline[0].key


def test_open_bucket_signature_grouping_and_max_members():
    p = OpenBucketPlanner(AX, waste_budget=0.5, max_members=2)
    b16 = p.offer(0, _inst(3, S=16))
    assert p.offer(1, _inst(3, S=20)) is not b16   # shape never mixes
    assert p.offer(2, _inst(3, S=16)) is b16
    assert p.offer(3, _inst(3, S=16)) is not b16   # occupancy cap hit
    assert len(p.open_buckets) == 3


def test_open_bucket_discard_shrinks_capacity():
    p = OpenBucketPlanner(AX, waste_budget=0.5)
    b = p.offer(0, _inst(3))
    p.offer(1, _inst(6))
    assert b.capacity == 6
    p.discard(b, 1)
    assert b.capacity == 3                    # back to largest remaining
    p.discard(b, 0)
    assert len(p.open_buckets) == 0           # emptied bucket closes
    with pytest.raises(ValueError, match="waste_budget"):
        OpenBucketPlanner(AX, waste_budget=1.0)


# ---------------------------------------------------------------------
# The end-to-end property the planner exists to protect
# ---------------------------------------------------------------------

def test_padded_solve_matches_unpadded_bitforbit():
    """A padded instance's valid region reproduces its unpadded single
    solve: zero records are trajectory-inert and the replicated derived
    state is built pre-padding.

    "Bit-for-bit" holds for the math, not for the compiled programs: the
    batched program (stacked lanes, freeze mask) is a different XLA
    program from the single solve, and XLA:CPU groups its fused
    elementwise arithmetic per program.  Even a lone unpadded lane
    differs from the single solve by 1 ulp of the stamp's peak after two
    iterations.  The bound is 4 ulp of the peak (measured: 2)."""
    from repro.core.problem import solve, solve_many
    from repro.imaging import psf as psf_op
    from repro.imaging.condat import SolverConfig

    cfg = SolverConfig(mode="sparse", max_iter=6, tol=0.0, n_scales=2)
    d3 = psf_op.simulate(3, jax.random.PRNGKey(0), stamp=16)
    d5 = psf_op.simulate(5, jax.random.PRNGKey(1), stamp=16)
    insts = [(d3.Y, d3.psfs), (d5.Y, d5.psfs)]   # one bucket, cap 5
    sols = solve_many("deconvolve", insts, cfg=cfg, chunk=3)
    assert len({b.key for b in plan_buckets(
        insts, BatchAxes(record_axes=(0, 0)))}) == 1
    for inst, sol in zip(insts, sols):
        ref = solve("deconvolve", *inst, cfg=cfg, chunk=3)
        assert sol.x.shape == ref.x.shape
        ulp = np.spacing(np.abs(np.asarray(ref.x)).max())
        np.testing.assert_allclose(np.asarray(sol.x), np.asarray(ref.x),
                                   rtol=0, atol=4 * ulp)
