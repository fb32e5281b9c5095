"""planner.rank_configs edge cases: divisibility, aliasing/stagger,
VMEM exhaustion, tie-break ordering."""
import jax.numpy as jnp
import pytest

from repro.core import layout
from repro.core.planner import Traffic, plan, rank_configs
from repro.core.striding import SINGLE_STRIDED


class _FlatModel:
    """Constant-bandwidth model: every config ties, exposing tie-breaks."""

    def throughput(self, config, block_bytes, spacing_bytes=None,
                   n_write_streams=0):
        return 1.0


def test_non_divisible_extent_restricts_stride_unrolls():
    # 7 is prime: the only divisors <= max_streams are 1 and 7
    ranked = rank_configs(Traffic(rows=7, cols=256))
    assert {cfg.stride_unroll for cfg, _, _ in ranked} <= {1, 7}
    # and every candidate respects the §5.1.2 divisibility constraint
    for cfg, _, _ in ranked:
        assert 7 % cfg.stride_unroll == 0


def test_vmem_budget_exhaustion_raises():
    with pytest.raises(ValueError, match="no feasible striding config"):
        rank_configs(Traffic(rows=64, cols=256), vmem_budget=1)


def test_resident_bytes_count_against_budget():
    t = Traffic(rows=64, cols=256, resident_bytes=10 * 2**20)
    with pytest.raises(ValueError):
        rank_configs(t, vmem_budget=8 * 2**20)


def test_tie_break_prefers_smaller_d_then_smaller_p():
    ranked = rank_configs(Traffic(rows=64, cols=256), model=_FlatModel())
    assert ranked[0][0] == SINGLE_STRIDED.replace(lookahead=2)
    order = [(c.stride_unroll, c.portion_unroll) for c, _, _ in ranked]
    assert order == sorted(order)


def test_aliased_pow2_spacing_pads_columns_when_possible():
    # rows=64, d=4 → 16-row segments; 256 f32 cols = 16 KiB spacing (2^14):
    # one lane tile of padding (cols=384) de-aliases it.
    cols, aliased = layout.conflict_free_cols(64, 256, 4, jnp.float32)
    assert not aliased
    assert cols == 384
    assert not layout.collides((64 // 4) * cols * 4)


def test_unpaddable_alias_triggers_column_stagger():
    # rows=64, d=8, cols=128 → 4 KiB spacing; with the pad budget capped
    # at one lane tile every candidate spacing (4 KiB, 8 KiB) stays an
    # exact power of two, so padding cannot help → the kernel must fall
    # back to a per-stream column stagger.
    cols, aliased = layout.conflict_free_cols(64, 128, 8, jnp.float32,
                                              max_pad_tiles=1)
    assert aliased
    assert cols == 128
    spacing = (64 // 8) * cols * 4
    stag = layout.stream_stagger(8, spacing, 512)
    assert stag > 0
    assert not layout.collides(spacing + stag * 512)


def test_rank_configs_scores_staggered_spacing_for_aliased_layouts():
    # The aliased d=8 point must still be rankable (spacing de-aliased by
    # one lane tile in the score), not dropped.
    ranked = rank_configs(Traffic(rows=64, cols=128))
    ds = {cfg.stride_unroll for cfg, _, _ in ranked}
    assert 8 in ds


def test_descriptor_overhead_seeded_from_env(monkeypatch):
    """REPRO_DMA_DESCRIPTOR_NS seeds the model's per-transfer descriptor
    term; unseeded, the default model is exactly TPU_V5E."""
    from repro.core.dma_model import TPU_V5E, default_tpu_model
    monkeypatch.delenv("REPRO_DMA_DESCRIPTOR_NS", raising=False)
    assert default_tpu_model() == TPU_V5E
    monkeypatch.setenv("REPRO_DMA_DESCRIPTOR_NS", "495.1")
    assert default_tpu_model().descriptor_overhead == pytest.approx(
        495.1e-9)


def test_seeded_descriptor_overhead_ranks_block_rows(monkeypatch):
    """The ranked block_rows ordering responds to the seeded descriptor
    term: a dominant per-transfer cost makes every (D, P) point's block
    candidates rank strictly by size (big tiles amortize descriptors),
    and the bandwidth gap between block sizes grows with the seed —
    testable without real v5e."""
    t = Traffic(rows=4096, cols=4096)

    def ranked_bw(ns):
        monkeypatch.setenv("REPRO_DMA_DESCRIPTOR_NS", str(ns))
        out = rank_configs(t, block_rows_candidates=(1, 32))
        return {(c.stride_unroll, c.portion_unroll, c.block_rows): bw
                for c, bw, _ in out}

    heavy = ranked_bw(50_000)       # 50 µs per descriptor dominates
    light = ranked_bw(0)
    for (d, p, bm), bw in heavy.items():
        if bm == 32:
            assert bw > heavy[(d, p, 1)]
    # the big-vs-small block advantage must grow with the seeded cost
    gain_heavy = heavy[(2, 1, 32)] / heavy[(2, 1, 1)]
    gain_light = light[(2, 1, 32)] / light[(2, 1, 1)]
    assert gain_heavy > gain_light > 1.0


def test_plan_returns_best_and_full_ranking():
    p = plan(Traffic(rows=64, cols=256))
    assert p.config == p.ranked[0][0]
    bws = [bw for _, bw in p.ranked]
    assert bws == sorted(bws, reverse=True)
    assert p.vmem_bytes > 0
