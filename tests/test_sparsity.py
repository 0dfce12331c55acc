"""ASP 2:4 sparsity tests (ref style: apex/contrib/test/sparsity)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from apex_tpu.contrib.sparsity import (
    ASP,
    apply_permutation,
    compute_sparse_masks,
    create_mask,
    exhaustive_search,
    fill,
    invert_permutation,
    m4n2_1d,
    m4n2_2d_best,
    masked_update,
    mn_1d_best,
    permute_and_mask,
    prune,
    search_for_good_permutation,
)


class TestMaskLib:
    def test_m4n2_keeps_top2_per_group(self, rng):
        x = jax.random.normal(rng, (8, 16))
        mask = m4n2_1d(x)
        m = np.asarray(mask).reshape(-1, 4)
        assert (m.sum(axis=1) == 2).all()
        # kept entries are the 2 largest |x| per group
        xs = np.abs(np.asarray(x)).reshape(-1, 4)
        for g in range(xs.shape[0]):
            kept = np.sort(xs[g][m[g] == 1])
            dropped = np.sort(xs[g][m[g] == 0])
            assert kept.min() >= dropped.max() - 1e-6

    def test_mn_patterns_other_ratios(self, rng):
        x = jax.random.normal(rng, (4, 8))
        mask = mn_1d_best(x, 2, 1)
        assert (np.asarray(mask).reshape(-1, 2).sum(axis=1) == 1).all()

    def test_create_mask_axis(self, rng):
        x = jax.random.normal(rng, (16, 8))
        mask = create_mask(x, axis=0)  # prune along dim 0
        assert (np.asarray(mask).T.reshape(-1, 4).sum(axis=1) == 2).all()
        with pytest.raises(ValueError):
            create_mask(x, pattern="nope")

    def test_2d_best_is_valid_rowwise(self, rng):
        x = jax.random.normal(rng, (16, 16))
        mask = m4n2_2d_best(x)
        assert (np.asarray(mask).reshape(-1, 4).sum(axis=1) == 2).all()

    def test_2d_best_is_valid_both_directions(self, rng):
        """The 2-D variant's whole purpose: the transpose (dgrad GEMM
        direction) is also 2:4 sparse (ref m4n2_2d_best)."""
        x = jax.random.normal(rng, (16, 24))
        mask = np.asarray(m4n2_2d_best(x))
        assert (mask.reshape(-1, 4).sum(axis=1) == 2).all()  # row-wise
        # column-wise: within each 4x4 block every column keeps exactly 2
        blocks = mask.reshape(4, 4, 6, 4).transpose(0, 2, 1, 3)
        assert (blocks.sum(axis=2) == 2).all()

    def test_2d_best_maximizes_retained_magnitude_per_block(self):
        # a block where the greedy row-then-repair approach is suboptimal:
        # exhaustive search must pick the doubly-balanced argmax
        from apex_tpu.contrib.sparsity import mn_2d_best
        from apex_tpu.contrib.sparsity.sparse_masklib import (
            compute_valid_2d_patterns,
        )

        rngn = np.random.RandomState(3)
        for _ in range(5):
            blk = rngn.randn(4, 4).astype(np.float32)
            mask = np.asarray(mn_2d_best(jnp.asarray(blk), 4, 2))
            pats = compute_valid_2d_patterns(4, 2).reshape(-1, 4, 4)
            best = max(float(np.sum(np.abs(blk) * p)) for p in pats)
            got = float(np.sum(np.abs(blk) * mask))
            assert got == pytest.approx(best, rel=1e-6)

    def test_2d_pattern_count(self):
        from apex_tpu.contrib.sparsity.sparse_masklib import (
            compute_valid_2d_patterns,
        )

        # doubly-balanced 4x4 matrices with row/col sums 2: exactly 90
        assert compute_valid_2d_patterns(4, 2).shape == (90, 16)

    def test_fill(self):
        assert fill(jnp.array([[1.0, 0.0], [0.0, 0.0]])) == 0.25


class TestASP:
    def make_params(self, rng):
        return {
            "dense": {"kernel": jax.random.normal(rng, (32, 16)),
                      "bias": jnp.ones((16,))},
            "norm": {"scale": jnp.ones((32,))},
            "small": {"kernel": jax.random.normal(rng, (4, 4))},
        }

    def test_compute_masks_eligibility(self, rng):
        params = self.make_params(rng)
        masks = compute_sparse_masks(params)
        # eligible: dense/kernel (reduction dim 32); others all-ones
        k = np.asarray(masks["dense"]["kernel"])
        assert (k.T.reshape(-1, 4).sum(axis=1) == 2).all()  # axis=-2
        assert (np.asarray(masks["dense"]["bias"]) == 1).all()
        assert (np.asarray(masks["norm"]["scale"]) == 1).all()
        assert (np.asarray(masks["small"]["kernel"]) == 1).all()

    def test_masked_update_preserves_sparsity(self, rng):
        params = self.make_params(rng)
        masks = compute_sparse_masks(params)
        params = prune(params, masks)
        opt = optax.chain(optax.adam(1e-2), masked_update(masks))
        state = opt.init(params)

        def loss_fn(p):
            return jnp.sum(p["dense"]["kernel"] ** 2) + jnp.sum(
                p["small"]["kernel"] ** 2
            )

        for _ in range(3):
            grads = jax.grad(loss_fn)(params)
            updates, state = opt.update(grads, state, params)
            params = optax.apply_updates(params, updates)
        k = np.asarray(params["dense"]["kernel"])
        zero_pat = np.asarray(masks["dense"]["kernel"]) == 0
        np.testing.assert_array_equal(k[zero_pat], 0.0)
        # unmasked leaves keep training normally
        assert np.abs(np.asarray(params["small"]["kernel"])).sum() > 0

    def test_class_api_prune_trained_model(self, rng):
        asp = ASP()
        assert not asp.is_sparsity_enabled()
        params = self.make_params(rng)
        pruned = asp.prune_trained_model(params)
        assert asp.is_sparsity_enabled()
        k = np.asarray(pruned["dense"]["kernel"])
        assert (np.abs(k).T.reshape(-1, 4) > 0).sum() <= 2 * (32 * 16 // 4)
        opt = asp.init_optimizer_for_pruning(optax.sgd(0.1))
        assert opt.init(pruned) is not None


class TestPermutation:
    def test_search_improves_adversarial_matrix(self):
        # columns arranged so each group of 4 holds 4 equally-large values
        # -> naive 2:4 drops half the magnitude; a permutation that spreads
        # them across groups with the near-zero columns retains almost all
        big = np.ones((8, 8)) * 10.0
        small = np.ones((8, 8)) * 0.01
        mat = np.concatenate([big, small], axis=1)  # groups 0,1 all-big

        def retained(m, mask):
            return float(np.sum(np.abs(m) * np.asarray(mask)))

        naive = retained(mat, m4n2_1d(jnp.asarray(mat)))
        perm, mask = permute_and_mask(mat, max_iters=2000)
        permuted_kept = retained(mat, mask)
        assert permuted_kept > naive * 1.5
        # permutation is a bijection and inverts correctly
        inv = invert_permutation(perm)
        x = jnp.arange(16.0)
        np.testing.assert_array_equal(
            apply_permutation(apply_permutation(x, perm), inv), x
        )

    def test_mask_in_original_order_is_2to4_after_perm(self):
        rngn = np.random.RandomState(0)
        mat = rngn.randn(8, 16).astype(np.float32)
        perm, mask = permute_and_mask(mat, max_iters=500)
        permuted_mask = np.asarray(apply_permutation(mask, perm, axis=-1))
        assert (permuted_mask.reshape(-1, 4).sum(axis=1) == 2).all()


def _retained_after_perm(mat, perm):
    a = np.abs(np.asarray(mat, dtype=np.float64))[:, perm].reshape(
        mat.shape[0], -1, 4
    )
    return float(np.partition(a, 2, axis=-1)[..., 2:].sum())


class TestExhaustiveSearch:
    """Parity with the reference stripe-group search (exhaustive_search.py
    Exhaustive_Search :311; unique-combination count :83-90)."""

    def test_canonical_combination_count(self):
        from apex_tpu.contrib.sparsity.permutation import (
            _unique_group_permutations,
        )

        # predict_unique_combinations: C! / ((M!)^G * G!)
        assert len(_unique_group_permutations(8, 4)) == 35
        assert len(_unique_group_permutations(4, 4)) == 1
        perms = _unique_group_permutations(8, 4)
        np.testing.assert_array_equal(perms[0], np.arange(8))  # identity first
        assert len({tuple(p) for p in map(tuple, perms)}) == 35

    def test_matches_brute_force_on_8_columns(self):
        """With one stripe pair the window IS the matrix: the search must
        find the global optimum over all 8!-column regroupings."""
        from apex_tpu.contrib.sparsity.permutation import (
            _unique_group_permutations,
            exhaustive_search,
        )

        rngn = np.random.RandomState(3)
        for _ in range(5):
            mat = rngn.randn(6, 8).astype(np.float32)
            perm = exhaustive_search(mat)
            got = _retained_after_perm(mat, perm)
            best = max(
                _retained_after_perm(mat, p)
                for p in _unique_group_permutations(8, 4)
            )
            assert got >= best - 1e-5, (got, best)

    def test_beats_or_ties_greedy_on_adversarial(self):
        """Retained magnitude >= greedy on
        adversarial matrices (clustered large columns, the case channel
        permutation exists for)."""
        rngn = np.random.RandomState(7)
        for cols in (16, 32):
            # adversarial: big columns clustered into aligned groups
            big = rngn.randn(16, cols // 2) * 10.0
            small = rngn.randn(16, cols // 2) * 0.01
            mat = np.concatenate([big, small], axis=1).astype(np.float32)
            g = search_for_good_permutation(mat, max_iters=4000)
            e = exhaustive_search(mat, escape_attempts=10)
            assert _retained_after_perm(mat, e) >= _retained_after_perm(
                mat, g
            ) - 1e-4

    def test_is_permutation_and_improves_or_ties_identity(self):
        rngn = np.random.RandomState(11)
        mat = rngn.randn(12, 24).astype(np.float32)
        perm = exhaustive_search(mat)
        assert sorted(perm.tolist()) == list(range(24))
        assert _retained_after_perm(mat, perm) >= _retained_after_perm(
            mat, np.arange(24)
        ) - 1e-6

    def test_escape_attempts_never_hurt(self):
        rngn = np.random.RandomState(13)
        mat = rngn.randn(8, 16).astype(np.float32)
        base = _retained_after_perm(mat, exhaustive_search(mat))
        esc = _retained_after_perm(
            mat, exhaustive_search(mat, escape_attempts=20)
        )
        assert esc >= base - 1e-6


class TestASPRegression:
    def test_late_bound_masks_reference_call_order(self, rng):
        """Reference order: init model -> init optimizer -> compute masks
        (asp.py:53-55) — the chain must see the masks computed LATER."""
        params = {"dense": {"kernel": jax.random.normal(rng, (32, 16))}}
        asp = ASP()
        asp.init_model_for_pruning(params)
        opt = asp.init_optimizer_for_pruning(optax.sgd(0.1))
        asp.compute_sparse_masks(params)  # after optimizer creation
        params = prune(params, asp.masks)
        state = opt.init(params)
        grads = jax.tree_util.tree_map(jnp.ones_like, params)
        updates, state = opt.update(grads, state, params)
        params = optax.apply_updates(params, updates)
        k = np.asarray(params["dense"]["kernel"])
        zero_pat = np.asarray(asp.masks["dense"]["kernel"]) == 0
        np.testing.assert_array_equal(k[zero_pat], 0.0)

    def test_masks_recomputed_after_jit_are_seen(self, rng):
        """Masks live in the optimizer STATE, so a step jitted before
        compute_sparse_masks still applies masks pushed in later via
        refresh_opt_state (the round-1 closure-constant hazard)."""
        from apex_tpu.contrib.sparsity import replace_masks

        params = {"dense": {"kernel": jax.random.normal(rng, (32, 16))}}
        asp = ASP()
        asp.init_model_for_pruning(params)
        opt = asp.init_optimizer_for_pruning(optax.sgd(0.1))
        state = opt.init(params)  # masks still all-ones here

        @jax.jit
        def step(params, state):
            grads = jax.tree_util.tree_map(jnp.ones_like, params)
            updates, state = opt.update(grads, state, params)
            return optax.apply_updates(params, updates), state

        params, state = step(params, state)  # trace with all-ones masks
        # late compute MUST take the live opt_state (r2 weak #7: the
        # silent-dense path is unrepresentable, not a warning)
        asp2 = ASP()
        asp2.init_model_for_pruning(params)
        opt2 = asp2.init_optimizer_for_pruning(optax.sgd(0.1))
        state2 = opt2.init(params)
        with pytest.raises(RuntimeError, match="stay dense"):
            asp2.compute_sparse_masks(params)
        # the sanctioned repair: retry with the live state, flag clears,
        # and refresh_opt_state keeps working as the manual form
        _, state2 = asp2.compute_sparse_masks(params, state2)
        asp2.compute_sparse_masks(params)  # no longer raises
        state2b = asp2.refresh_opt_state(state2)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
            state2b, state2,
        )
        _, state = asp.compute_sparse_masks(params, state)
        params = prune(params, asp.masks)
        params, state = step(params, state)  # same trace, new masks
        k = np.asarray(params["dense"]["kernel"])
        zero_pat = np.asarray(asp.masks["dense"]["kernel"]) == 0
        np.testing.assert_array_equal(k[zero_pat], 0.0)
        # replace_masks is a no-op on states without a MaskedState
        plain = optax.sgd(0.1).init(params)
        assert replace_masks(plain, asp.masks) == plain

    def test_prune_trained_model_after_dense_training(self, rng):
        """The reference one-shot recipe (ref asp.py:292) after a dense run
        whose optimizer was initialized on placeholder masks: passing the
        live opt_state returns (pruned_params, refreshed_state)."""
        params = {"dense": {"kernel": jax.random.normal(rng, (32, 16))}}
        asp = ASP()
        asp.init_model_for_pruning(params)
        opt = asp.init_optimizer_for_pruning(optax.sgd(0.1))
        state = opt.init(params)  # placeholder masks
        pruned, state = asp.prune_trained_model(params, state)
        k = np.asarray(pruned["dense"]["kernel"])
        assert ((np.abs(k).T.reshape(-1, 4) > 0).sum(axis=1) <= 2).all()
        # the refreshed state drives sparse updates from here on
        grads = jax.tree_util.tree_map(jnp.ones_like, pruned)
        updates, state = opt.update(grads, state, pruned)
        after = optax.apply_updates(pruned, updates)
        zero_pat = np.asarray(asp.masks["dense"]["kernel"]) == 0
        np.testing.assert_array_equal(
            np.asarray(after["dense"]["kernel"])[zero_pat], 0.0
        )

    def test_embeddings_never_pruned(self, rng):
        params = {
            "embedding": {"embedding": jax.random.normal(rng, (64, 32))},
            "embed_tokens": {"weight": jax.random.normal(rng, (64, 32))},
            "proj": {"kernel": jax.random.normal(rng, (64, 32))},
        }
        masks = compute_sparse_masks(params)
        assert (np.asarray(masks["embedding"]["embedding"]) == 1).all()
        assert (np.asarray(masks["embed_tokens"]["weight"]) == 1).all()
        k = np.asarray(masks["proj"]["kernel"])
        assert (k.T.reshape(-1, 4).sum(axis=1) == 2).all()
