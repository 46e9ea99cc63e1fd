"""The controls: the reference put in the system's place with one stated
guarantee broken has to come out as not correct, on three seeds, by the
same comparisons the runs use. (At the cells' own sizes: controls.py.)"""

import pytest

from benchmarks.tests import controls

SEEDS = (3, 2_147_483_777, 4_000_000_019)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("broken", ["oldest_version_kept", "expired_kept"])
def test_compaction_control_is_not_correct(seed, broken):
    fill = dict(controls.load_config("compact10m")["fill"], records=40_000)
    got = controls.compaction_control(seed, fill, 100, broken)
    assert got["reference_against_itself"] == 0
    assert got["rows_differing"] > 0 or got["point_reads_wrong"] > 0


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("broken", ["stale_reads", "lose_every",
                                    "alter_every"])
def test_served_control_is_not_correct(seed, broken):
    sound = controls.served_control(seed, None, hashkeys=20, seconds=0.4)
    assert sound["reads_wrong"] == sound["updates_lost"] == 0
    assert sound["untouched_changed"] == 0 and sound["operations"] > 100
    bad = controls.served_control(seed, broken, hashkeys=20, seconds=0.4)
    assert (bad["reads_wrong"] + bad["updates_lost"]
            + bad["untouched_changed"]) > 0


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("broken", ["lose_every", "alter_every"])
def test_read_only_control_is_not_correct(seed, broken):
    """`ycsb1kb.c`'s mix: the only acknowledged writes are the load's."""
    mix = {"read": 1.0, "update": 0.0}
    sound = controls.served_control(seed, None, hashkeys=20, seconds=0.4,
                                    mix=mix)
    assert sound["reads_wrong"] == sound["updates_lost"] == 0
    assert sound["untouched_changed"] == 0 and sound["operations"] > 100
    bad = controls.served_control(seed, broken, hashkeys=20, seconds=0.4,
                                  mix=mix)
    assert bad["updates_lost"] == 0        # by construction: no update
    assert bad["reads_wrong"] > 0
    if broken == "lose_every":
        assert bad["untouched_changed"] > 0
