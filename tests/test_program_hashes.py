"""The lowered programs of the tiny families are the recorded ones: a change
that is meant to move no program on the device is held to `program_hashes.json`,
which was generated from the commit BEFORE it (`tests/program_hashes.py`)."""

import jax
import pytest

from program_hashes import FAMILIES, family_hashes, load_golden


@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_familys_lowered_programs_are_the_recorded_ones(family):
    golden = load_golden().get(jax.__version__)
    if golden is None:
        pytest.skip(f"program_hashes.json holds no table for jax {jax.__version__}")
    got = family_hashes(family)
    moved = sorted(name for name in golden[family].keys() | got.keys() if golden[family].get(name) != got.get(name))
    assert not moved, (
        f"{family}: the lowered text of {', '.join(moved)} is not the recorded one. Where the change is meant to "
        "move no program, it moved one. Where a program is meant to move, regenerate the table from the change: "
        f"`JAX_PLATFORMS=cpu python tests/program_hashes.py --write {family}`")
