"""Fused pointer-init kernel inside the distributed hot path: labels and
the local doubling rounds stay bit-identical to the jnp init on ragged
corpus cases, for the single-request AND the batched (vmap-inside-shard_map)
entry points.

Runs in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main test process keeps its single-device view.
"""
import os
import subprocess
import sys
import textwrap

_ROOT = os.path.join(os.path.dirname(__file__), "..")

_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    sys.path.insert(0, os.path.join(%(root)r, "tests"))
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import (make_dpc_mesh, distributed_manifold,
                            distributed_connected_components,
                            distributed_manifold_batch,
                            distributed_connected_components_batch)
    from oracles import ragged_grid_case

    assert len(jax.devices()) == 8
    failures = []

    def corpus_3d(max_cases):
        out, seed = [], 0
        while len(out) < max_cases and seed < 64:
            shape, layout, conn, mask_p = ragged_grid_case(seed)
            if len(shape) == 3:
                out.append((seed, shape, layout, conn, mask_p))
            seed += 1
        return out

    for seed, shape, layout, conn, mask_p in corpus_3d(2):
        rng = np.random.default_rng(seed)
        mesh = make_dpc_mesh(layout)
        order = jnp.asarray(rng.permutation(int(np.prod(shape)))
                            .reshape(shape).astype(np.int32))
        mask = jnp.asarray(rng.random(shape) < mask_p)

        l0, s0 = distributed_manifold(order, mesh, conn, fused_impl="ref")
        l1, s1 = distributed_manifold(order, mesh, conn, fused_impl="kernel")
        if not (np.asarray(l0) == np.asarray(l1)).all():
            failures.append(("manifold", seed))
        # the same init bits give the same local doubling rounds
        if int(s1.local_iters) != int(s0.local_iters):
            failures.append(("manifold-iters", seed))

        c0, t0 = distributed_connected_components(mask, mesh, conn,
                                                  fused_impl="ref")
        c1, t1 = distributed_connected_components(mask, mesh, conn,
                                                  fused_impl="kernel")
        if not (np.asarray(c0) == np.asarray(c1)).all():
            failures.append(("cc", seed))
        if int(t1.local_iters) != int(t0.local_iters):
            failures.append(("cc-iters", seed))

    # batched: one ragged 3-D case, per-item bit-identity vs single-request
    seed, shape, layout, conn, mask_p = corpus_3d(1)[0]
    rng = np.random.default_rng(100 + seed)
    mesh = make_dpc_mesh(layout)
    B = 3
    orders = jnp.stack([jnp.asarray(rng.permutation(int(np.prod(shape)))
                                    .reshape(shape).astype(np.int32))
                        for _ in range(B)])
    masks = jnp.stack([jnp.asarray(rng.random(shape) < mask_p)
                       for _ in range(B)])
    bl, bs = distributed_manifold_batch(orders, mesh, conn,
                                        fused_impl="kernel")
    bc, bt = distributed_connected_components_batch(masks, mesh, conn,
                                                    fused_impl="kernel")
    for i in range(B):
        li, _ = distributed_manifold(orders[i], mesh, conn,
                                     fused_impl="kernel")
        if not (np.asarray(bl[i]) == np.asarray(li)).all():
            failures.append(("batch-manifold", i))
        ci, _ = distributed_connected_components(masks[i], mesh, conn,
                                                 fused_impl="kernel")
        if not (np.asarray(bc[i]) == np.asarray(ci)).all():
            failures.append(("batch-cc", i))

    assert not failures, failures
    print("FUSED-DIST-OK")
""") % {"root": _ROOT}


def test_fused_distributed_matches_oracles():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", _WORKER], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "FUSED-DIST-OK" in proc.stdout
