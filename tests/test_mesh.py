"""MeshConfig / create_mesh unit coverage (the multi-process integration
legs live in tests/test_multihost.py)."""

import pytest

from bert_pytorch_tpu.parallel import MeshConfig, create_mesh


def test_resolve_dcn_divides_data_axis():
    # 16 devices, dcn_data=2: the ICI granule holds 8-way data parallelism.
    assert MeshConfig(dcn_data=2).resolve(16) == (8, 1, 1, 1, 1, 1)
    # explicit data size is the PER-GRANULE size
    assert MeshConfig(data=4, dcn_data=2, model=2).resolve(16) == \
        (4, 1, 1, 1, 2, 1)


def test_resolve_dcn_divisibility_errors():
    with pytest.raises(ValueError, match="dcn_data"):
        MeshConfig(dcn_data=3).resolve(16)
    with pytest.raises(ValueError, match="dcn"):
        MeshConfig(data=8, dcn_data=2).resolve(8)


def test_create_mesh_dcn_needs_granules(devices):
    # Single-process CPU: one process granule cannot satisfy dcn_data=2.
    with pytest.raises(ValueError, match="[Nn]umber of slices"):
        create_mesh(MeshConfig(dcn_data=2, dcn_process_granule=True))


def test_create_mesh_plain_shapes(devices):
    import jax

    mesh = create_mesh(MeshConfig(data=2, seq=2, model=2),
                       devices=jax.devices()[:8])
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
        "data": 2, "fsdp": 1, "pipe": 1, "seq": 2, "model": 2, "expert": 1}


def test_current_mesh_reads_the_with_block(devices):
    """parallel/mesh.py ``current_mesh`` is the package's one use of a
    private jax name (``jax._src.mesh.thread_resources``): this pins what
    it is relied on for — the concrete mesh of the enclosing ``with mesh:``
    block, also while tracing under ``jit`` — so that a jax upgrade that
    moves it fails here and not inside ring attention."""
    import jax

    from bert_pytorch_tpu.parallel import current_mesh

    assert current_mesh() is None
    mesh = create_mesh(MeshConfig(data=4, seq=2), devices=jax.devices()[:8])
    seen = []

    def traced(x):
        seen.append(current_mesh())
        return x

    with mesh:
        assert current_mesh() == mesh
        jax.jit(traced)(1)
    assert seen == [mesh]
    assert current_mesh() is None


def test_the_package_imports_one_private_jax_name():
    import os
    import re

    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bert_pytorch_tpu")
    found = []
    for folder, _, files in os.walk(root):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path) as f:
                for line in f:
                    if re.match(r"\s*(from|import)\s+jax\._src", line):
                        found.append((os.path.relpath(path, root),
                                      line.strip()))
    assert found == [(os.path.join("parallel", "mesh.py"),
                      "from jax._src.mesh import thread_resources")]
