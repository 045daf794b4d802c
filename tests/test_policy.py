"""Policy chain (paper §3, Eq. 1): worked examples + hypothesis invariants."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_shim import given, settings, st

from repro.core.policy import (Level, Policy, chain_elements,
                               compute_job_shares_from_table,
                               transition_matrices)
from repro.core.job_table import make_table

J = 16


def shares(policy_name, jobs, demand=None):
    t = make_table(jobs, max_jobs=J)
    d = None if demand is None else jnp.asarray(
        np.array(demand + [False] * (J - len(demand))))
    return np.asarray(compute_job_shares_from_table(Policy.parse(policy_name), t, d))


class TestPaperExamples:
    def test_fig3a_job_fair(self):
        s = shares("job-fair", [{}, {}])
        np.testing.assert_allclose(s[:2], [0.5, 0.5], atol=1e-6)

    def test_fig3b_user_then_job_fair(self):
        jobs = [{"user": 0}] * 2 + [{"user": 1}] * 4
        s = shares("user-then-job-fair", jobs)
        np.testing.assert_allclose(s[:6], [0.25, 0.25, 0.125, 0.125, 0.125, 0.125], atol=1e-6)

    def test_fig5_size_fair_global(self):
        s = shares("size-fair", [{"size": 16}, {"size": 8}, {"size": 8}])
        np.testing.assert_allclose(s[:3], [0.5, 0.25, 0.25], atol=1e-6)

    def test_fig4_transition_matrix_rows_sum_to_one(self):
        jobs = [{"user": 0}] * 2 + [{"user": 1}] * 4
        t = make_table(jobs, max_jobs=J)
        mats = transition_matrices(
            Policy.parse("user-then-job-fair"),
            active=t.active, user_id=t.user_id, group_id=t.group_id,
            size=t.size, priority=t.priority)
        assert mats[0].shape == (1, J)
        np.testing.assert_allclose(float(mats[0].sum()), 1.0, atol=1e-6)
        row_sums = np.asarray(mats[1].sum(axis=1))
        live_rows = row_sums > 0
        np.testing.assert_allclose(row_sums[live_rows], 1.0, atol=1e-6)
        # only one non-zero entry per column (an entity has one parent)
        nz_per_col = (np.asarray(mats[1]) > 0).sum(axis=0)
        assert nz_per_col.max() <= 1

    def test_priority_fair(self):
        s = shares("priority-fair", [{"priority": 3.0}, {"priority": 1.0}])
        np.testing.assert_allclose(s[:2], [0.75, 0.25], atol=1e-6)

    def test_group_user_size(self):
        # paper §5.3.2: 2 groups, users in groups, jobs sized; check the tree
        jobs = [
            {"group": 0, "user": 0, "size": 2}, {"group": 0, "user": 0, "size": 3},
            {"group": 1, "user": 1, "size": 1}, {"group": 1, "user": 2, "size": 1},
        ]
        s = shares("group-user-size-fair", jobs)
        # group0 = 0.5 -> user0 = 0.5 -> jobs 2:3 -> 0.2, 0.3
        # group1 = 0.5 -> users 1,2 get 0.25 each -> their single jobs 0.25
        np.testing.assert_allclose(s[:4], [0.2, 0.3, 0.25, 0.25], atol=1e-6)


class TestOpportunityFairness:
    def test_demand_mask_redistributes_within_scope_first(self):
        # user-fair: user0 {j0, j1}, user1 {j2}. j1 idle => j0 takes user0's
        # whole half; flat renorm would wrongly give j0 only 1/3.
        jobs = [{"user": 0}, {"user": 0}, {"user": 1}]
        s = shares("user-fair", jobs, demand=[True, False, True])
        np.testing.assert_allclose(s[:3], [0.5, 0.0, 0.5], atol=1e-6)

    def test_whole_scope_idle_escalates(self):
        jobs = [{"user": 0}, {"user": 0}, {"user": 1}]
        s = shares("user-fair", jobs, demand=[False, False, True])
        np.testing.assert_allclose(s[:3], [0.0, 0.0, 1.0], atol=1e-6)

    def test_no_demand_gives_zeros(self):
        s = shares("job-fair", [{}, {}], demand=[False, False])
        np.testing.assert_allclose(s, 0.0, atol=1e-6)


class TestPolicyParsing:
    def test_named_policies(self):
        for name in ["job-fair", "size-fair", "user-fair", "priority-fair",
                     "user-then-size-fair", "group-then-user-fair",
                     "group-user-size-fair"]:
            p = Policy.parse(name)
            assert p.levels[-1].entity == "job"

    def test_chain_syntax(self):
        p = Policy.parse("group:fair,user:fair,job:size")
        assert p.depth == 3 and p.levels[2].weight == "size"

    def test_invalid_order_rejected(self):
        with pytest.raises(ValueError):
            Policy((Level("job"), Level("user"), Level("job")))

    def test_fifo_is_not_a_policy(self):
        with pytest.raises(ValueError):
            Policy.parse("fifo")

    def test_misspelled_named_policy_fails_loudly(self):
        """A typo'd name must not fall through to the chain parser — the
        error lists the known named policies and the chain grammar."""
        with pytest.raises(ValueError, match="Known named policies"):
            Policy.parse("user-fiar")
        with pytest.raises(ValueError) as ei:
            Policy.parse("size_fair")
        msg = str(ei.value)
        assert "size-fair" in msg and "entity" in msg

    def test_misspelled_chain_entity_fails_loudly(self):
        with pytest.raises(ValueError, match="Known named policies"):
            Policy.parse("grp:fair,job:fair")

    def test_bare_entity_chain_still_parses(self):
        """Backward compatibility: chain specs with real entities (weight
        defaulting to fair, job level auto-appended) keep working."""
        p = Policy.parse("user")
        assert [l.entity for l in p.levels] == ["user", "job"]
        p = Policy.parse("group:size")
        assert p.levels[0].weight == "size"


# The multi-tenant deployment's 64 slots: 3 groups and 12 users.  Group 0:
# user 0 has 24 jobs, user 1 has 8; group 1: users 2-5 have 8, 6, 4, 2;
# group 2: users 6-11 have 2 each.
TENANT_USERS = ([0] * 24 + [1] * 8 + [2] * 8 + [3] * 6 + [4] * 4 + [5] * 2
                + [u for u in range(6, 12) for _ in range(2)])
TENANT_GROUPS = [0] * 32 + [1] * 20 + [2] * 12


class TestChainPrecision:
    """Eq. 1 at full precision: the product is the one the benchmark's plain
    reference takes (``bench.reference.chain_shares``)."""

    def test_every_product_is_taken_at_highest_precision(self):
        t = make_table([{"group": g, "user": u} for g, u in
                        zip(TENANT_GROUPS, TENANT_USERS)], max_jobs=64)
        text = jax.jit(lambda d: compute_job_shares_from_table(
            Policy.parse("group-then-user-fair"), t, d)).lower(
                jnp.ones((64,), bool)).as_text()
        dots = [line for line in text.splitlines() if "dot_general" in line]
        assert len(dots) == 3
        assert all("precision = [HIGHEST, HIGHEST]" in d for d in dots)

    @pytest.mark.parametrize("live", ["all", "every_other", "one_group_idle"])
    def test_tenant_shares_equal_the_reference_bit_for_bit(self, live):
        from bench.reference import chain_shares

        mask = {"all": np.ones(64, bool),
                "every_other": np.arange(64) % 2 == 0,
                "one_group_idle": np.array(TENANT_GROUPS) != 1}[live]
        jobs = [{"group": g, "user": u}
                for g, u in zip(TENANT_GROUPS, TENANT_USERS)]
        got = np.asarray(jax.jit(lambda d: compute_job_shares_from_table(
            Policy.parse("group-then-user-fair"), make_table(jobs, 64), d))(
                jnp.asarray(mask)))
        want = np.asarray(jax.jit(functools.partial(
            chain_shares, ("group", "user", "job"),
            user=jnp.asarray(TENANT_USERS), group=jnp.asarray(TENANT_GROUPS),
            fdt=jnp.float32))(jnp.asarray(mask)))
        np.testing.assert_array_equal(got, want)
        assert got.sum() == pytest.approx(1.0, abs=1e-6)
        if live == "all":
            # 1/3 per group; user 0's 24 jobs 1/144 ... users 6-11's 1/36,
            # user 5's two jobs 1/24.
            expect = np.float32([1 / 144] * 24 + [1 / 48] * 8 + [1 / 96] * 8
                                + [1 / 72] * 6 + [1 / 48] * 4 + [1 / 24] * 2
                                + [1 / 36] * 12)
            np.testing.assert_allclose(got, expect, rtol=2e-7)


@pytest.mark.parametrize("name,n,elements", [
    ("job-fair", 8, 8),
    ("user-then-job-fair", 8, 8 + 8 * 8),
    # Composite ids: [1, 64] + [64, 64²] + [64², 64].
    ("group-then-user-fair", 64, 64 + 2 * 64 ** 3)])
def test_chain_elements_are_the_built_matrices_elements(name, n, elements):
    """The counter is read off what ``transition_matrices`` builds, so a
    change to the matrices' shapes moves it with no second formula."""
    policy = Policy.parse(name)
    t = make_table([{"group": i % 3, "user": i % 5} for i in range(n)],
                   max_jobs=n)
    mats = transition_matrices(policy, active=t.active, user_id=t.user_id,
                               group_id=t.group_id, size=t.size,
                               priority=t.priority)
    assert sum(m.size for m in mats) == chain_elements(policy, n) == elements


@st.composite
def job_specs(draw):
    n = draw(st.integers(1, 12))
    jobs = [
        {
            "user": draw(st.integers(0, 4)),
            "group": draw(st.integers(0, 2)),
            "size": draw(st.integers(1, 64)),
            "priority": draw(st.floats(0.5, 8.0, allow_nan=False)),
        }
        for _ in range(n)
    ]
    demand = draw(st.lists(st.booleans(), min_size=n, max_size=n))
    return jobs, demand


@st.composite
def policies(draw):
    use_group = draw(st.booleans())
    use_user = draw(st.booleans())
    levels = []
    if use_group:
        levels.append(Level("group", draw(st.sampled_from(["fair", "size"]))))
    if use_user:
        levels.append(Level("user", draw(st.sampled_from(["fair", "size"]))))
    levels.append(Level("job", draw(st.sampled_from(["fair", "size", "priority"]))))
    return Policy(tuple(levels))


class TestPolicyProperties:
    @settings(max_examples=60, deadline=None)
    @given(job_specs(), policies())
    def test_shares_are_a_distribution(self, spec, policy):
        jobs, demand = spec
        t = make_table(jobs, max_jobs=J)
        d = jnp.asarray(np.array(demand + [False] * (J - len(jobs))))
        s = np.asarray(compute_job_shares_from_table(policy, t, d))
        assert (s >= -1e-6).all()
        assert (s[~np.asarray(d)] <= 1e-6).all(), "idle jobs must get zero share"
        total = s.sum()
        assert total == pytest.approx(1.0, abs=1e-5) or (not any(demand) and total == pytest.approx(0.0, abs=1e-6))

    @settings(max_examples=40, deadline=None)
    @given(job_specs())
    def test_user_fair_splits_by_user(self, spec):
        jobs, demand = spec
        t = make_table(jobs, max_jobs=J)
        d = jnp.asarray(np.array(demand + [False] * (J - len(jobs))))
        s = np.asarray(compute_job_shares_from_table(Policy.parse("user-fair"), t, d))
        users = {}
        for j, (job, dem) in enumerate(zip(jobs, demand)):
            if dem:
                users.setdefault(job["user"], 0.0)
                users[job["user"]] += s[j]
        if users:
            per_user = np.array(list(users.values()))
            np.testing.assert_allclose(per_user, 1.0 / len(users), atol=1e-5)

    @settings(max_examples=40, deadline=None)
    @given(job_specs())
    def test_size_fair_proportional(self, spec):
        jobs, demand = spec
        t = make_table(jobs, max_jobs=J)
        d = jnp.asarray(np.array(demand + [False] * (J - len(jobs))))
        s = np.asarray(compute_job_shares_from_table(Policy.parse("size-fair"), t, d))
        sizes = np.array([job["size"] if dem else 0 for job, dem in zip(jobs, demand)], float)
        if sizes.sum() > 0:
            np.testing.assert_allclose(s[: len(jobs)], sizes / sizes.sum(), atol=1e-5)
