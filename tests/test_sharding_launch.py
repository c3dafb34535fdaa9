"""Logical sharding resolution, cell construction, and (subprocess) the
multi-device distributed pieces: majority all-reduce, compressed train step,
reduced-config cell lowering on an 8-device host mesh."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (DEFAULT_RULES, constrain, resolve_spec,
    strip_axes)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeMesh:
    """Just enough mesh interface for resolve_spec (axis names + shape)."""

    def __init__(self, **axes):
        import numpy as _np
        self.axis_names = tuple(axes)
        self.devices = _np.empty(tuple(axes.values()), object)


def test_resolve_spec_basic():
    m = FakeMesh(data=16, model=16)
    assert resolve_spec((256, 4096), ("batch", "seq"), m, DEFAULT_RULES) \
        == P("data", None)
    assert resolve_spec((8192, 16384), ("fsdp", "mlp"), m, DEFAULT_RULES) \
        == P("data", "model")


def test_resolve_spec_multi_axis_batch():
    m = FakeMesh(pod=2, data=16, model=16)
    spec = resolve_spec((256, 128), ("batch", None), m, DEFAULT_RULES)
    assert spec == P(("pod", "data"), None)


def test_resolve_spec_divisibility_fallback():
    m = FakeMesh(data=16, model=16)
    # kv_heads=8 cannot shard 16 ways -> replicated
    assert resolve_spec((1024, 8, 128), ("fsdp", "kv_heads", "head_dim"),
                        m, DEFAULT_RULES) == P("data", None, None)
    # batch=1 (long_500k decode) -> replicated
    assert resolve_spec((1, 524288), ("batch", "seq"), m,
                        DEFAULT_RULES) == P(None, None)
    # kv_flat=1024 divides 16
    assert resolve_spec((32, 1024), (None, "kv_flat"), m,
                        DEFAULT_RULES) == P(None, "model")


def test_resolve_spec_no_axis_reuse():
    m = FakeMesh(data=4, model=4)
    # two logical names mapping to "model": second one must NOT reuse it
    spec = resolve_spec((64, 64), ("heads", "mlp"), m, DEFAULT_RULES)
    assert spec == P("model", None)


def test_strip_axes():
    rules = strip_axes(DEFAULT_RULES, ("data", "pod"))
    assert rules["batch"] == ()
    assert rules["vocab"] == ("model",)


def test_constrain_identity_outside_context():
    x = jnp.ones((4, 4))
    y = constrain(x, "batch", None)
    assert y is x


def test_param_spec_trees_cover_all_leaves():
    """Every param leaf of every arch has a logical spec of matching rank."""
    from repro.configs.base import ARCH_IDS, get_config, reduced
    from repro.models import build
    for arch in ARCH_IDS:
        cfg = reduced(get_config(arch))
        shapes, specs = build(cfg).abstract()
        flat_p = jax.tree.leaves(shapes)
        flat_s = jax.tree.leaves(specs,
                                 is_leaf=lambda x: isinstance(x, tuple))
        assert len(flat_p) == len(flat_s), arch
        for p, s in zip(flat_p, flat_s):
            assert len(s) == p.ndim, (arch, p.shape, s)


def test_full_config_abstract_no_alloc():
    """abstract() on the FULL kimi-k2 1T config must not allocate."""
    from repro.configs.base import get_config
    from repro.models import build
    shapes, specs = build(get_config("kimi_k2_1t_a32b")).abstract()
    n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    assert n > 0.9e12   # ~1T params


def test_input_specs_all_cells():
    from repro.configs.base import SHAPES, cells, get_config
    from repro.models import input_specs
    for arch, shape in cells():
        cfg = get_config(arch)
        specs = input_specs(cfg, SHAPES[shape])
        assert all(hasattr(l, "shape")
                   for l in jax.tree.leaves(specs)), (arch, shape)


_SUBPROC_CELL = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, {repo!r} + "/src")
    import jax
    from repro.configs.base import ShapeConfig
    from repro.launch.cells import build_cell
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    shape = ShapeConfig("t", 64, 8, {kind!r})
    cell = build_cell({arch!r}, "train_4k", mesh, reduce_config=True,
                      shape_override=shape)
    compiled = cell.lower().compile()
    print("COMPILED_OK", compiled.cost_analysis() is not None)
""")


@pytest.mark.parametrize("arch,kind", [("qwen3_0p6b", "train"),
                                       ("mamba2_1p3b", "decode"),
                                       ("kimi_k2_1t_a32b", "train")])
def test_cell_lowers_on_host_mesh(arch, kind):
    """Reduced-config cells lower+compile on an 8-device host mesh (the
    full-size 512-device version is exercised by launch/dryrun.py)."""
    code = _SUBPROC_CELL.format(repo=REPO, arch=arch, kind=kind)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=420)
    assert "COMPILED_OK" in r.stdout, r.stderr[-2000:]


def test_majority_allreduce_subprocess():
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, {REPO!r} + "/src")
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim.signum import majority_allreduce, pack_tree, unpack_tree
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        D = 8
        xs = jax.random.normal(jax.random.PRNGKey(0), (D, 333))
        def worker(x):
            packed, meta = pack_tree({{"g": x[0]}}, use_kernel=False)
            agg = majority_allreduce(packed, "data", use_kernel=False)
            return unpack_tree(agg, meta, use_kernel=False)["g"][None]
        f = jax.jit(jax.shard_map(worker, mesh=mesh, in_specs=P("data"),
                                  out_specs=P("data"), axis_names={{"data"}},
                                  check_vma=False))
        out = np.asarray(f(xs))
        neg = (np.asarray(xs) < 0).sum(0)
        expect = np.where(neg * 2 > D, -1.0, 1.0)
        for d in range(D):
            assert np.array_equal(out[d], expect), d
        print("MAJORITY_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300)
    assert "MAJORITY_OK" in r.stdout, r.stderr[-2000:]
