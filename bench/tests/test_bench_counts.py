"""The operation and byte counts of both configurations against hand
counts, and the plain reference forward passes against the program's."""
import dataclasses

import numpy as np
import pytest

from bench_tiny import harness

CNN = ("fedsr-cnn", 319_178,
       # conv0 32x32x32x27 + conv1 16x16x64x288 + conv2 8x8x64x576
       # + fc0 4096x64 + fc1 64x10 multiply-adds
       884_736 + 4_718_592 + 2_359_296 + 262_144 + 640)
MLP = ("fedsr-mlp", 199_210, 784 * 200 + 200 * 200 + 200 * 10)


def _files(name):
    cfg = harness.read_json(harness.BENCH / "configs" / f"{name}.json")
    return cfg, harness.load_module(harness.BENCH / "configs" / f"{name}.py")


@pytest.mark.parametrize("name,params,macs", [CNN, MLP])
def test_counts_match_hand_counts(name, params, macs):
    cfg, counts = _files(name)
    assert counts.param_count(cfg) == params == cfg["params"]
    assert counts.forward_flops(cfg) == 2 * macs
    first = 884_736 if name == "fedsr-cnn" else 784 * 200
    # weight gradients of every layer, input gradients of all but the first
    assert counts.train_flops(cfg) == 2 * (2 * macs + macs - first)
    side = cfg["image_size"]
    assert counts.image_bytes(cfg) == 4 * side * side * cfg["image_channels"] + 4


def test_cnn_forward_is_16_4_mflop():
    cfg, counts = _files("fedsr-cnn")
    assert counts.forward_flops(cfg) == pytest.approx(16.45e6, rel=1e-3)
    cfg, counts = _files("fedsr-mlp")
    assert counts.forward_flops(cfg) == pytest.approx(0.3976e6, rel=1e-3)


@pytest.mark.parametrize("name", ["fedsr-cnn", "fedsr-mlp"])
def test_weights_and_reference_match_program(name):
    """The benchmark's weights have the program's tree, and the plain
    reference forward gives the program's logits."""
    import jax
    import jax.numpy as jnp

    from bench import compare, gen
    from repro.configs import get_config
    from repro.models.small import small_model_apply

    cfg, counts = _files(name)
    w = gen.init_model(123, cfg)
    compare.same_structure(w, get_config(cfg["registry"]))
    assert sum(x.size for x in jax.tree.leaves(w)) == cfg["params"]
    ref = harness.load_module(harness.BENCH / "reference" / f"{name}.py")
    side, ch = cfg["image_size"], cfg["image_channels"]
    x = jax.random.uniform(jax.random.PRNGKey(1), (4, side, side, ch))
    # nonzero biases, so that the reference's bias placement is tested
    w = jax.tree.map(lambda v: v + 0.01 if v.ndim == 1 else v, w)
    with jax.default_matmul_precision("highest"):
        got = small_model_apply(w, x, get_config(cfg["registry"]))
        want = ref.apply(w, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert jnp.all(jnp.isfinite(got))


@pytest.mark.parametrize("name", ["fedsr-cnn", "fedsr-mlp"])
def test_model_config_is_the_registry_entry(name):
    """A configuration that states no change runs the registry's entry,
    field for field."""
    from repro.configs import get_config
    cfg, _ = _files(name)
    assert "program" not in cfg
    assert harness.model_config(cfg) == get_config(cfg["registry"])


def test_model_config_takes_the_fields_the_file_maps():
    from repro.configs import get_config
    cfg, _ = _files("fedsr-mlp")
    cfg = dict(cfg, mlp_hidden=[64, 32], program={"mlp_hidden": "mlp_hidden"})
    got = harness.model_config(cfg)
    assert got.mlp_hidden == (64, 32)
    assert got == dataclasses.replace(get_config("fedsr-mlp"),
                                      mlp_hidden=(64, 32))
    with pytest.raises(KeyError, match="no field 'hidden'"):
        harness.model_config(dict(cfg, program={"hidden": "mlp_hidden"}))


def test_generator_is_seeded_and_shaped():
    from bench import gen
    data = {"num_classes": 10, "image_size": 28, "channels": 1,
            "train_per_class": 3, "test_per_class": 2, "noise": 0.15}
    (a, la), (t, lt) = gen.image_task(2**33 + 5, data)
    (b, lb), _ = gen.image_task(2**33 + 5, data)
    (c, _), _ = gen.image_task(2**33 + 6, data)
    assert a.shape == (30, 784) and t.shape == (20, 784)
    assert gen.host_images(a, data).shape == (30, 28, 28, 1)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    assert np.bincount(np.asarray(la)).tolist() == [3] * 10
    assert 0.0 <= float(a.min()) and float(a.max()) <= 1.0


def test_generator_draws_scaled_shifted_templates():
    """Without noise, every image is its class template rolled by -2..2
    pixels on each axis and scaled by a factor in [0.7, 1.3] (then
    clipped), as in the repo's data/synthetic."""
    import jax

    from bench import gen
    data = {"num_classes": 4, "image_size": 12, "channels": 3,
            "train_per_class": 5, "test_per_class": 1, "noise": 0.0}
    (x, y), _ = gen.image_task(77, data)
    kt = jax.random.split(gen.key_from_seed(77, 1), 3)[0]
    tmpl = np.asarray(gen._templates(kt, 4, 12, 3))
    assert tmpl.min() == 0.0 and abs(tmpl.max() - 1.0) < 1e-6
    imgs = gen.host_images(x, data)
    for img, label in zip(imgs, np.asarray(y)):
        fits = []
        for sy in range(-2, 3):
            for sx in range(-2, 3):
                rolled = np.roll(tmpl[label], (sy, sx), axis=(0, 1))
                ok = (rolled > 0.05) & (img < 1.0)
                ratio = img[ok] / rolled[ok]
                fits.append((np.ptp(ratio), ratio.mean()))
        spread, scale = min(fits)
        assert spread < 1e-4 and 0.7 <= scale <= 1.3
