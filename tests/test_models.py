"""Model zoo shape checks (reference has only cv/test_cnn.py, a 13-LoC
shape test; here every factory entry gets one)."""
import jax
import jax.numpy as jnp
import pytest

from fedml_tpu.models import create_model

IMG32 = (2, 32, 32, 3)
IMG28 = (2, 28, 28, 1)


def _forward(model, shape, train=False, **init_kw):
    """init+apply under jit: eager dispatch of the deep zoo models costs
    tens of seconds per test on CPU and is uncacheable; as two compiled
    programs the persistent compilation cache (conftest) makes warm suite
    runs near-instant."""
    x = jnp.zeros(shape, jnp.float32)
    init = jax.jit(lambda k, xi: model.init(k, xi, train=False, **init_kw))
    variables = init(jax.random.PRNGKey(0), x)
    if train:
        apply = jax.jit(lambda v, xi, k: model.apply(
            v, xi, train=True, rngs={"dropout": k},
            mutable=["batch_stats"]))
        return apply(variables, x, jax.random.PRNGKey(1))[0]
    apply = jax.jit(lambda v, xi: model.apply(v, xi, train=False))
    return apply(variables, x)


@pytest.mark.parametrize("name,shape,classes", [
    ("mobilenet_v3", IMG32, 10),
    ("efficientnet-b0", IMG32, 10),
])
def test_new_cv_models_forward(name, shape, classes):
    logits = _forward(create_model(name, classes), shape)
    assert logits.shape == (shape[0], classes)
    assert jnp.all(jnp.isfinite(logits))


@pytest.mark.parametrize("name,shape,classes", [
    ("lr", (2, 784), 10),
    ("cnn", (2, 28, 28, 1), 62),
    ("cnn_dropout", (2, 28, 28, 1), 62),
    ("resnet18_gn", IMG32, 10),
    ("resnet20", IMG32, 10),
    ("resnet56", IMG32, 100),
    ("mobilenet", IMG32, 10),
    ("vgg11", IMG32, 10),
    ("vgg16", IMG32, 10),
])
def test_full_zoo_forward(name, shape, classes):
    """Every --model factory name produces finite logits of the right
    shape (reference model zoo §2.6 row-by-row)."""
    logits = _forward(create_model(name, classes), shape)
    assert logits.shape == (shape[0], classes)
    assert jnp.all(jnp.isfinite(logits))


@pytest.mark.parametrize("name,vocab,seq", [
    ("rnn", 90, 80),
    ("rnn_stackoverflow", 10004, 20),
])
def test_zoo_rnn_forward(name, vocab, seq):
    m = create_model(name, vocab)
    x = jnp.zeros((2, seq), jnp.int32)
    v = m.init(jax.random.PRNGKey(0), x, train=False)
    out = m.apply(v, x, train=False)
    assert out.shape == (2, seq, vocab)
    assert jnp.all(jnp.isfinite(out))


def test_mobilenet_v3_small_mode():
    m = create_model("mobilenet_v3", 10, mode="small")
    logits = _forward(m, IMG32)
    assert logits.shape == (2, 10)


def test_efficientnet_train_mode_with_drop_connect():
    m = create_model("efficientnet-b0", 10)
    logits = _forward(m, IMG32, train=True)
    assert logits.shape == (2, 10)
    assert jnp.all(jnp.isfinite(logits))


def test_efficientnet_variant_scaling():
    from fedml_tpu.models.efficientnet import PARAMS
    assert set(PARAMS) == {f"b{i}" for i in range(8)}


def test_factory_rejects_unknown():
    with pytest.raises(ValueError):
        create_model("no_such_model", 10)
