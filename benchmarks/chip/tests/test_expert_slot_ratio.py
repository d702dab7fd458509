"""The reader of the program's expert row counters (``expert_slot_ratio``)
on hand-made records, and on shares of a program that keeps no such
counter (the parent of the counters, or a config with no experts)."""
import importlib
import types

import pytest

DOC = {"first_k_dense_replace": 1, "n_routed_experts": 8,
       "ladder": [{"num_hidden_layers": 9}]}


def _read(*shares, doc=DOC):
    ctx = types.SimpleNamespace(
        records=[types.SimpleNamespace(shares=list(shares))], doc=doc)
    return importlib.import_module("metrics.expert_slot_ratio").read(ctx)


def _share(rows, slots, level=0):
    return types.SimpleNamespace(level=level, expert_rows=rows,
                                 expert_slots=slots)


def test_slots_over_routed_rows(capsys):
    # 8 MoE layers of 4096 tokens, top-8 over 256, 8 held: 32,768 rows
    # given a layer, about 1,024 routed
    assert _read(_share(8192, 262144), _share(8000, 262144)) == \
        pytest.approx(2 * 262144 / 16192)
    assert "126.5 rows per held expert" in capsys.readouterr().err


def test_nothing_to_read():
    bare = types.SimpleNamespace(level=0, served=8)     # no counters
    assert _read(bare) is None
    assert _read() is None
    assert _read(_share(0, 0)) is None                  # no expert routed
