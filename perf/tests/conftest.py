"""The benchmark's own tests run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest perf/tests -q
"""
import os
import sys

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import pytest  # noqa: E402


@pytest.fixture
def cell_stands_in_its_lists():
    """Presence, not place (PR 45): ``check(cell, families, own)``
    holds that the cell's name stands in the ``workloads`` of every
    per-layer entry in ``families`` (entries it shares with other
    cells), that the entries in ``own`` list this cell alone, that
    all of them move ``moves``, and that every entry the cell
    reports resolves to a reader file. Returns the cell's entries by
    name."""
    from perf.lib import harness

    def check(cell, families, own, moves='out_tok_s'):
        mine = {m['name']: m
                for m in harness.load_cell(cell)['per_layer']}
        assert set(mine) >= families | own, \
            (families | own) - set(mine)
        assert all(mine[name]['workloads'] == [cell] for name in own)
        assert {mine[name]['moves'] for name in families | own} == \
            {moves}
        for name in mine:
            harness.reader_for(name, harness.PERF_DIR)
        return mine
    return check
