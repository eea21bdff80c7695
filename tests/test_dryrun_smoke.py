"""Dry-run machinery on a small (2, 4) mesh: every arch family's cells
build + lower + compile, roofline terms parse.  (The full 16x16 / 2x16x16
sweeps run via ``python -m repro.launch.dryrun``; their results live in
experiments/dryrun/.)"""

import pytest


@pytest.mark.slow
@pytest.mark.parametrize("arch,shape", [
    ("qwen2.5-3b", "decode_32k"),
    ("schnet", "molecule"),
    ("bst", "retrieval_cand"),
])
def test_cell_compiles_small_mesh(subproc, arch, shape):
    subproc(f"""
import jax
from repro.distributed.mesh_utils import make_mesh
from repro.launch.steps import build_cell
from repro.launch import roofline as RL
mesh = make_mesh((2, 4), ("data", "model"))
cell = build_cell("{arch}", "{shape}", mesh)
with mesh:
    compiled = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                       donate_argnums=cell.donate).lower(*cell.args).compile()
cost = compiled.cost_analysis()
assert float(cost.get("flops", 0)) > 0
coll = RL.collective_bytes_from_hlo(compiled.as_text())
roof = RL.analyze_terms(float(cost["flops"]),
                        float(cost.get("bytes accessed", 0)), coll, 8,
                        model_flops=RL.model_flops_for(cell.cfg, cell.shape))
assert roof.bottleneck in ("compute", "memory", "collective")
print("CELL OK", "{arch}", "{shape}")
""", timeout=900)


def test_all_cells_enumerate():
    from repro.launch.steps import all_cells

    cells = all_cells()
    assert len(cells) == 40
    archs = {a for a, _ in cells}
    assert len(archs) == 10


def test_collective_parser():
    from repro.launch.roofline import collective_bytes_from_hlo, _shape_bytes

    hlo = """
  %all-reduce.1 = f32[8,128]{1,0} all-reduce(%x), replica_groups={}
  %ag = bf16[2,64]{1,0} all-gather(%y), dimensions={0}
  %not-a-collective = f32[4]{0} add(%a, %b)
  %aa = (f32[16]{0}, f32[16]{0}) all-to-all(%p, %q)
"""
    out = collective_bytes_from_hlo(hlo)
    assert out["all-reduce"] == 8 * 128 * 4
    assert out["all-gather"] == 2 * 64 * 2
    assert out["all-to-all"] == 2 * 16 * 4
    assert _shape_bytes("pred[3,5]") == 15


@pytest.mark.slow
def test_production_mesh_shapes(subproc):
    subproc("""
from repro.launch.mesh import make_production_mesh
m1 = make_production_mesh()
assert m1.devices.shape == (16, 16) and m1.axis_names == ("data", "model")
m2 = make_production_mesh(multi_pod=True)
assert m2.devices.shape == (2, 16, 16)
assert m2.axis_names == ("pod", "data", "model")
print("MESH OK")
""", n_devices=512)
