"""Relay collectives: correctness on 8 simulated devices (subprocess so the
main test process keeps its single CPU device), plus the analytic model."""
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conftest import jax_subprocess_env
from repro.core.relay_collectives import (estimate_naive_time,
                                          estimate_relay_time)


def test_relay_beats_naive_fanout_analytically():
    """The paper's argument: relaying beats 2× reads of the slow source.
    In-mesh: pipelined chain vs source fan-out over P destinations."""
    bw = 50e9
    for p in (2, 4, 8):
        relay = estimate_relay_time(1e9, bw, p, n_chunks=8)
        naive = estimate_naive_time(1e9, bw, p)
        assert relay <= naive + 1e-9
    # pipelining: more chunks -> closer to single-transfer time
    t2 = estimate_relay_time(1e9, bw, 8, n_chunks=2)
    t16 = estimate_relay_time(1e9, bw, 8, n_chunks=16)
    assert t16 < t2


_SUBPROC = textwrap.dedent("""
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core.relay_collectives import (relay_broadcast_inner,
                                              naive_broadcast_inner,
                                              ring_all_gather_inner)
    import functools

    mesh = jax.make_mesh((8,), ("pod",))
    x = jnp.arange(8 * 16 * 4, dtype=jnp.float32).reshape(8 * 16, 4)
    # stacked along pod: slice p holds rows [16p, 16p+16); src slice = 0

    fn = jax.jit(jax.shard_map(
        functools.partial(relay_broadcast_inner, axis_name="pod",
                          axis_size=8, src=0, n_chunks=4),
        mesh=mesh, in_specs=(P("pod"),), out_specs=P("pod"),
        check_vma=False))
    out = np.asarray(fn(x)).reshape(8, 16, 4)
    src_block = np.asarray(x[:16])
    for p in range(8):
        np.testing.assert_array_equal(out[p], src_block)
    print("RELAY_OK")

    fn2 = jax.jit(jax.shard_map(
        functools.partial(naive_broadcast_inner, axis_name="pod",
                          axis_size=8, src=0),
        mesh=mesh, in_specs=(P("pod"),), out_specs=P("pod"),
        check_vma=False))
    out2 = np.asarray(fn2(x)).reshape(8, 16, 4)
    for p in range(8):
        np.testing.assert_array_equal(out2[p], src_block)
    print("NAIVE_OK")

    y = jnp.arange(8 * 4.0, dtype=jnp.float32).reshape(8, 4)
    fn3 = jax.jit(jax.shard_map(
        functools.partial(ring_all_gather_inner, axis_name="pod", axis_size=8),
        mesh=mesh, in_specs=(P("pod"),), out_specs=P("pod"),
        check_vma=False))
    out3 = np.asarray(fn3(y)).reshape(8, 8, 4)
    for p in range(8):
        np.testing.assert_array_equal(out3[p], np.asarray(y))
    print("RING_OK")

    # HLO structure: relay lowers to collective-permutes only
    txt = fn.lower(x).compile().as_text()
    assert "collective-permute" in txt
    print("HLO_OK")
""")


def test_relay_collectives_on_8_devices():
    r = subprocess.run([sys.executable, "-c", _SUBPROC], cwd=".",
                       env=jax_subprocess_env(devices=8),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    for marker in ("RELAY_OK", "NAIVE_OK", "RING_OK", "HLO_OK"):
        assert marker in r.stdout, (marker, r.stdout, r.stderr[-2000:])


def test_compressed_psum_on_4_devices():
    code = textwrap.dedent("""
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        import functools
        from jax.sharding import PartitionSpec as P
        from repro.optim.grad_compress import psum_compressed
        mesh = jax.make_mesh((4,), ("pod",))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
        fn = jax.jit(jax.shard_map(
            functools.partial(psum_compressed, axis_name="pod"),
            mesh=mesh, in_specs=(P("pod"),), out_specs=P("pod"),
            check_vma=False))
        out = np.asarray(fn(g)).reshape(4, 32)
        want = np.mean(np.asarray(g).reshape(4, 32), axis=0)
        for p in range(4):
            err = np.max(np.abs(out[p] - want))
            assert err < np.max(np.abs(g)) / 127 + 1e-6, err
        print("COMPRESS_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], cwd=".",
                       env=jax_subprocess_env(devices=4),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "COMPRESS_OK" in r.stdout
