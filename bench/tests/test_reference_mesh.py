"""The plain reference's trainer on a four-device mesh (CPU host devices):
the weight trees it makes from the seed, at set-up and for the change
norms, are sharded as its live leaves are, so no device holds a whole
tree; and its change norms equal those of one device."""
import os
import subprocess
import sys
import textwrap

from bench.common import ROOT, SRC

SEED = 2**31 + 41

CHECK = textwrap.dedent("""
    import sys
    import jax
    import numpy as np
    sys.path.insert(0, {root!r})
    from bench.common import load_json
    from bench.reference import qwen3 as R
    from bench.tests import tiny

    made = []
    real = R.make_weights

    def spy(*a, **kw):
        w = real(*a, **kw)
        made.append(w)
        return w

    R.make_weights = spy
    c = tiny.CONFIG
    hyp = load_json("workloads", "train-1chip")["optimizer"]
    rng = np.random.default_rng(0)
    rows = [rng.integers(0, c["vocab_size"], (8, 33)).astype(np.int32)
            for _ in range(2)]
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("x",))

    def norms(mesh, placed=None):
        tr = R.Trainer(c, {seed}, hyp, "f32", mesh)
        if placed is not None:  # the live leaves as set-up placed them
            for path, _, _ in R.leaf_table(c):
                a = (R._get(tr.layers[0], path[1:]) if path[0] == "blocks"
                     else R._get(tr.top, path))
                placed[path] = (a.shape, a.sharding)
        for r in rows:
            tr.step(r[:, :-1], r[:, 1:])
        return tr.change_norms({seed})

    placed = {{}}
    four = norms(mesh, placed)
    assert len(made) == 2, len(made)  # set-up, change norms
    for w in made:
        for path, shape, _ in R.leaf_table(c):
            got = R._get(w, path).sharding
            lshape, live = placed[path]
            want = live.shard_shape(lshape)
            if path[0] == "blocks":
                want = (shape[0],) + want
            assert got.device_set == live.device_set, path
            assert got.shard_shape(shape) == want, (path, got, want)
    one = norms(None)
    assert set(one) == set(four)
    worst = max(abs(four[k] - one[k]) for k in one)
    assert worst <= 1e-6, worst
    print("OK", len(one), worst)
""")


def test_mesh_seed_trees_are_sharded_and_norms_match_one_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(SRC))
    p = subprocess.run(
        [sys.executable, "-c", CHECK.format(root=str(ROOT), seed=SEED)],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert "OK" in p.stdout, p.stdout[-2000:]
