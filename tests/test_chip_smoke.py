"""chip_smoke.py's phases on the CPU at n = 2,000, Pallas in interpret mode.

The phases run here exactly as on the chip, with smaller request and write
counts; only the Mosaic check needs the chip.  ``main`` itself must refuse
the CPU, print no result and name the platform.
"""
import importlib.util
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke_mod():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_cpu(smoke_mod, capsys):
    assert smoke_mod.main([]) != 0
    out, err = capsys.readouterr()
    assert "'cpu'" in err
    assert '"ok"' not in out


def test_phases_at_small_n(smoke_mod):
    s = smoke_mod
    smoke = s.Smoke()
    data = s.make_data(2000, 0, n_queries=32, n_writes=64)
    assert data["x"].shape == (2000, s.DIM) and data["qv"].shape == (32, s.DIM)
    assert data["new_x"].shape == (64, s.DIM)
    idx = s.build(smoke, data, 0)
    ids, planes = s.serve_planes(smoke, idx, data, backend="pallas")
    assert set(planes) == {"f32", "int8", "pq"}
    assert planes["pq"].store.plane.data.shape[1] == s.PQ_M
    s.compare_xla(smoke, idx, data, ids)
    s.small_ef(smoke, idx, data, backend="pallas")
    s.writes(smoke, idx, data, 0, backend="pallas", chunk=32)
    assert smoke.failures == []


def test_mosaic_kernel_names(smoke_mod):
    text = ('  %expand_score_q.1 = f32[8,128]{1,0} custom-call(%a), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={}\n'
            '  %beam_merge = f32[8,128]{1,0} custom-call(%b), '
            'custom_call_target="tpu_custom_call"\n'
            '  %fusion.3 = f32[8]{0} fusion(%c), kind=kLoop\n')
    assert smoke_mod.mosaic_kernels(text) == {"expand_score_q", "beam_merge"}
