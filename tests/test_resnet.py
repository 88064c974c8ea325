import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.models import get_model, available_models
from distributeddeeplearning_tpu.models.resnet import ResNet, resnet_v1


def _init(model, size=32):
    rng = jax.random.PRNGKey(0)
    x = jnp.zeros((2, size, size, 3), jnp.float32)
    return model.init(rng, x, train=False), x


def _param_count(params):
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def test_registry_has_resnet_family():
    names = available_models()
    for d in (18, 34, 50, 101, 152, 200):
        assert f"resnet{d}" in names


def test_forward_shape_fp32_logits():
    model = get_model("resnet18", num_classes=10)
    variables, x = _init(model)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32


def test_resnet50_param_count_matches_reference():
    # torchvision resnet50 (the reference PyTorch model,
    # imagenet_pytorch_horovod.py:323) has 25,557,032 params; our v1
    # builder must match exactly (same architecture, bias-free convs).
    model = ResNet(depth=50, num_classes=1000, dtype=jnp.float32)
    variables, _ = _init(model, size=64)
    assert _param_count(variables["params"]) == 25_557_032


def test_resnet18_param_count_matches_reference():
    model = ResNet(depth=18, num_classes=1000, dtype=jnp.float32)
    variables, _ = _init(model, size=64)
    assert _param_count(variables["params"]) == 11_689_512  # torchvision resnet18


def test_zero_init_residual_gamma():
    # reference resnet_model.py:150,201 zero-inits the last BN gamma of
    # each residual branch.
    model = ResNet(depth=18, num_classes=10)
    variables, _ = _init(model)
    bn2 = variables["params"]["stage1_block1"]["BatchNorm_1"]
    np.testing.assert_array_equal(np.asarray(bn2["scale"]), 0.0)


def test_bad_depth_raises():
    model = ResNet(depth=77)
    with pytest.raises(ValueError, match="depth"):
        _init(model)


def test_resnet_v1_factory():
    m = resnet_v1(34, num_classes=7)
    assert m.depth == 34 and m.num_classes == 7


def test_batch_stats_update_in_train_mode():
    model = ResNet(depth=18, num_classes=10)
    variables, x = _init(model)
    x = jax.random.normal(jax.random.PRNGKey(1), x.shape)
    _, mutated = model.apply(variables, x, train=True, mutable=["batch_stats"])
    before = jax.tree.leaves(variables["batch_stats"])
    after = jax.tree.leaves(mutated["batch_stats"])
    assert any(not np.allclose(b, a) for b, a in zip(before, after))


def test_bfloat16_compute_f32_params():
    model = ResNet(depth=18, num_classes=10, dtype=jnp.bfloat16)
    variables, x = _init(model)
    for leaf in jax.tree.leaves(variables["params"]):
        assert leaf.dtype == jnp.float32
    out = model.apply(variables, x, train=False)
    assert out.dtype == jnp.float32


def test_resnet50_default_tree_is_what_a_checkpoint_restores_into():
    """Paths, shapes and dtypes of ResNet50's ``params`` and
    ``batch_stats``, written out from the architecture (flax's automatic
    names in a bottleneck block: ``Conv_0..2``, ``BatchNorm_0..2``, the
    projection under ``proj_conv`` / ``proj_bn``): a checkpoint of any
    earlier build restores into this tree leaf for leaf."""
    classes = 1000
    params = {"stem_conv/kernel": (7, 7, 3, 64), "head/kernel": (2048, classes),
              "head/bias": (classes,)}
    norms = {"stem_bn": 64}
    cin = 64
    for stage, blocks in enumerate((3, 4, 6, 3)):
        f = 64 * 2**stage
        for b in range(blocks):
            name = f"stage{stage + 1}_block{b + 1}"
            convs = {"Conv_0": (1, 1, cin, f), "Conv_1": (3, 3, f, f),
                     "Conv_2": (1, 1, f, 4 * f)}
            widths = {"BatchNorm_0": f, "BatchNorm_1": f, "BatchNorm_2": 4 * f}
            if b == 0:
                convs["proj_conv"] = (1, 1, cin, 4 * f)
                widths["proj_bn"] = 4 * f
            params.update({f"{name}/{k}/kernel": v for k, v in convs.items()})
            norms.update({f"{name}/{k}": v for k, v in widths.items()})
            cin = 4 * f
    params.update({f"{k}/{leaf}": (c,) for k, c in norms.items() for leaf in ("scale", "bias")})
    stats = {f"{k}/{leaf}": (c,) for k, c in norms.items() for leaf in ("mean", "var")}

    model = get_model("resnet50", num_classes=classes)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)), train=False)
    )

    def flat(tree):
        return {
            "/".join(k.key for k in path): (leaf.shape, leaf.dtype)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
        }

    assert sorted(shapes) == ["batch_stats", "params"]
    assert flat(shapes["params"]) == {k: (v, jnp.float32) for k, v in params.items()}
    assert flat(shapes["batch_stats"]) == {k: (v, jnp.float32) for k, v in stats.items()}
