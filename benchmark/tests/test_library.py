"""A configuration's library from generators found by name: the
`general-thresholds` library is byte for byte the one its module-level
generators built before they moved to files of their own, and a name
with no file raises, naming the file looked for."""

import hashlib
import json
import os
import re

import pytest

from benchmark import spec
from benchmark.gen import library
from benchmark.tests import tiny

WORKLOAD = "audit.general-thresholds"

# sha256 of the library's canonical JSON (sorted keys, no spaces), as
# the generators inlined in library.py built it before the move
TINY = {
    0: "8606655919d8cfdb1895d9dcc3f6f21647ce3d4d9341519d428c9edb23dafdb2",
    7: "f8541c29f2d2fda896a1cf5515faa133743dedee779fd108c7d9fe8431d00940",
    2**31 + 5:
        "a3de29a370c5a4aa8c79502952cd593fecdeab7d3755d9ef74f06de7e8cf14cc",
}
FULL = {
    2**31 + 11:
        "d1946ffa03b56cc0311ca2c6d5669544698ed65a75a3fd9b39568b006b86facd",
}


def digest(lib: dict) -> str:
    return hashlib.sha256(json.dumps(lib, sort_keys=True,
                                     separators=(",", ":")).encode()
                          ).hexdigest()


@pytest.mark.parametrize("seed", sorted(TINY))
def test_tiny_library_unchanged(tmp_path, seed):
    cell = spec.resolve(WORKLOAD, tiny.make_root(str(tmp_path)))
    lib = library.build(cell.config, seed, root=cell.root)
    assert len(lib["inventory"]) == 850
    assert digest(lib) == TINY[seed]


@pytest.mark.parametrize("seed", sorted(FULL))
def test_full_library_unchanged(seed):
    cell = spec.resolve(WORKLOAD)
    lib = library.build(cell.config, seed, root=cell.root)
    assert len(lib["inventory"]) == 170000
    assert digest(lib) == FULL[seed]


@pytest.mark.parametrize("group", ["constraints", "inventory"])
def test_unknown_generator_names_the_file(group):
    cell = spec.resolve(WORKLOAD)
    config = dict(cell.config, **{group: [{"generator": "no-such"}]})
    want = os.path.join(cell.root, "gen", group, "no-such.py")
    with pytest.raises(ValueError, match="no file " + re.escape(want)):
        library.build(config, 1, root=cell.root)
