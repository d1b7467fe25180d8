"""The pure parts of the chip tools that read a run's record: where a
long tick's time went (``tools/long_ticks.py``) and whose side a witness
takes (``tools/latent_witness.py``). The tools' runs themselves need the
chip."""

from __future__ import annotations

import numpy as np
import pytest

from tools import latent_witness, long_ticks


def region(name, t0, ms, parent=None):
    attrs = {"t0": t0, "ms": ms}
    if parent:
        attrs["parent"] = parent
    return {"name": name, "t": t0 + ms / 1e3, "attrs": attrs}


def test_a_long_ticks_time_is_laid_under_the_regions_inside_it():
    """Three ticks, the second of 2 s of which a fetch holds 1.9 s and the
    retire inside nothing else 0.05 s: one row, the regions summed by
    name, the tick's own 50 ms outside them, the generator's 10 ms before
    it; the short ticks give no row, nor does an event without a span."""
    events = [
        region("serve.tick", 10.0, 100.0),
        region("serve.fetch", 10.01, 80.0, "serve.tick"),
        region("serve.fetch", 10.12, 1900.0, "serve.tick"),
        region("serve.pool_write", 12.03, 20.0, "serve.admit"),
        region("serve.retire", 12.05, 50.0, "serve.tick"),
        region("serve.tick", 10.11, 2000.0),
        region("serve.tick", 12.12, 100.0),
        {"name": "dispatch", "t": 11.0, "attrs": {"family": "decode[T=4]"}},
    ]
    state = {"events": events, "t_open": 9.5, "t_close": 13.0}
    assert long_ticks.report(state, 5000.0) == []
    (row,) = long_ticks.report(state, 1000.0)
    assert row["tick_ms"] == 2000.0 and row["generator_before_ms"] == 10.0
    assert row["s_after_open"] == pytest.approx(0.61)
    assert list(row["regions"]) == ["serve.fetch", "serve.retire",
                                    "serve.pool_write"]
    assert row["regions"]["serve.fetch"] == {"ms": 1900.0, "n": 1,
                                             "longest_ms": 1900.0}
    # the pool write lies under serve.admit, not directly under the tick
    assert row["tick_outside_its_regions_ms"] == 50.0
    assert row["ticks_in_window"] == 3 and row["tick_ms_median"] == 100.0
    assert row["tick_ms_longest_other"] == 100.0


def test_the_windows_tile_fill_is_pairs_over_rows():
    """Pairs over rows of the window's ``dispatch`` events that carry the
    counters: a block outside the window and one that counts no routing
    stay out; a run with none gives no line."""
    def block(t, **attrs):
        return {"name": "dispatch", "t": t, "attrs": attrs}

    events = [block(10.0, expert_pairs=512.0, expert_rows=1024.0),
              block(11.0, expert_pairs=500.0, expert_rows=1056.0),
              block(11.5, family="decode[T=4]"),
              block(20.0, expert_pairs=1.0, expert_rows=512.0)]
    state = {"events": events, "t_open": 9.5, "t_close": 13.0}
    (row,) = long_ticks.tile_fill(state)
    assert row["dispatches"] == 2
    assert row["expert_pairs_mean"] == 506.0
    assert row["expert_rows_mean"] == 1040.0
    assert row["expert_tile_fill_pct"] == pytest.approx(100 * 506 / 1040)
    assert long_ticks.tile_fill(dict(state, events=events[2:3])) == []


@pytest.mark.parametrize("first,takes", [
    (3, "the served token's side"), (5, "the reference's side"),
    (1, "neither side")])
def test_a_witness_takes_the_side_of_the_token_it_puts_first(first, takes):
    ref = np.array([0.0, 0.1, 0.2, 0.6, 0.0, 1.0])      # best: 5; served: 3
    logits = np.zeros(6)
    logits[first] = 2.0
    got = latent_witness.side("w", logits, ref, served=3)
    assert got["takes"] == takes and got["reference_best"] == 5
    assert got["gap_of_its_first_below_the_references_best"] == \
        pytest.approx(1.0 - ref[first])


def test_routing_rows_name_the_swapped_expert_and_its_margin():
    """Top 2 of 6, experts 0-2 held: the reference takes 4 and 1 with 0
    behind by 0.03, the program's scores put 0 past 1: the row names both
    and says a held expert was swapped; the dense layer gives no row."""
    sz = {"held": (0, 3), "top_k": 2}
    ref = np.array([0.50, 0.53, 0.1, 0.2, 0.9, 0.3])
    got = ref + np.array([0.02, -0.02, 0.0, 0.0, 0.001, 0.0])
    (row,) = latent_witness.routing_rows(sz, [None, ref], {"block1": got})
    assert row["layer"] == 1 and not row["same_choice"]
    assert row["swapped"] == [0, 1] and row["a_swapped_expert_is_held"]
    assert row["reference_margin_last_chosen_to_first_left_out"] == \
        pytest.approx(0.03)
    assert row["score_gap_max"] == pytest.approx(0.02)
    # expert 1 is chosen 0.03 over expert 0, expert 0 left out 0.03 under
    assert row["reference_nearest_held_expert_to_the_far_side"] == \
        pytest.approx(0.03)
    same = latent_witness.routing_rows(sz, [ref], {"block0": ref})[0]
    assert same["same_choice"] and same["swapped"] == []


def test_the_state_checks_pure_parts_hold_at_a_tiny_size():
    """``tools/state_chip_check.py``: the kernel against its oracle, in
    the interpreter at 8 slots of 128 channels (the chip's run compiles it
    at 128 x 2,048), and the cell's live lengths as the tool draws them:
    inside the cache, a mean between the prompts' and the answers'."""
    from tools import state_chip_check

    assert state_chip_check.conv_check(8, 128, interpret=True) == 0.0
    lengths = state_chip_check.cell_lengths(128)
    assert lengths.shape == (128,) and lengths.min() >= 256
    assert lengths.max() <= state_chip_check.ROWS - 64
    assert 1000 < lengths.mean() < 2000
