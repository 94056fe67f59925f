"""The benchmark's copy of the Perlin generator makes the program's field,
and a field made block by block on several devices is the whole field."""
import sys

import numpy as np
import pytest

from conftest import REPO, run_python

sys.path.insert(0, str(REPO / "bench"))
sys.path.insert(0, str(REPO / "src"))


@pytest.mark.parametrize("shape,seed,origin", [
    ((9, 7, 5), 0, None), ((40, 6, 3), 3, None), ((8, 8, 8), 0, (5, 0, 2)),
    ((12, 10), 7, (1000003, 17))])
def test_copy_matches_program_generator(shape, seed, origin):
    import fields
    from repro.data.perlin import perlin_noise_device
    got = np.asarray(fields.perlin_noise_device(shape, 0.1, seed, origin))
    want = np.asarray(perlin_noise_device(shape, 0.1, seed, origin))
    np.testing.assert_array_equal(got, want)


def test_blocks_on_a_mesh_make_the_whole_field(tmp_path):
    proc = run_python(REPO, """
        import numpy as np
        import jax
        import fields
        from repro.core import make_dpc_mesh
        cfg = {"grid": [16, 12, 8], "layout": [2, 2], "frequency": 0.1,
               "origin": [1000003, 17, 5]}
        mesh = make_dpc_mesh((2, 2), devices=jax.devices()[:4])
        f = fields.make_field(cfg, mesh)
        assert len(f.sharding.device_set) == 4
        whole = fields.perlin_noise_device(
            (16, 12, 8), 0.1, 0, (1000003, 17, 5))
        np.testing.assert_allclose(np.asarray(f), np.asarray(whole),
                                   rtol=0, atol=1e-6)
        print("ok")
    """)
    assert proc.returncode == 0 and "ok" in proc.stdout, proc.stderr[-3000:]
