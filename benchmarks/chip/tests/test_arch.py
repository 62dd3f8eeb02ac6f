"""An architecture is found by the configuration's ``reference`` key
alone: a toy second one, added as two new modules (a bigram model),
reaches the program's configuration, the counts and the reference with
no edit to any existing file; an unknown name raises and lists the known
architectures."""
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import toy
from harness import arch, counts, seeds, spec, train, weights
from reference import train as ref_train

HARNESS_SIDE = '''
def model_config(config):
    return ("bigram", config["name"])

def layer_matmul_params(cfg):
    return 0

def n_params(cfg):
    return 2 * cfg["vocab_size"] * cfg["hidden_size"]

def attention_flops(cfg, position):
    return 0

def train_flops_per_step(cfg, batch, seq):
    return 3 * batch * seq * 2 * cfg["hidden_size"] * cfg["vocab_size"]
'''

REFERENCE_SIDE = '''
import jax
import jax.numpy as jnp

F32 = jnp.float32


def exact(spec, a, b):
    return jnp.einsum(spec, a.astype(F32), b.astype(F32),
                      precision=jax.lax.Precision.HIGHEST)


MATMULS = {"exact": exact}


def frozen(cfg):
    return tuple(sorted(cfg.items()))


def hidden(cfg, params, tokens, mm=exact):
    return jnp.take(params["embed"], tokens, axis=0).astype(F32)


def loss(cfg, params, tokens, labels, mm=exact):
    z = mm("bsd,dv->bsv", hidden(cfg, params, tokens), params["head"])
    gold = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=-1) - gold)


def logits_rows(cfg_items, params, h_rows, mm_name):
    return MATMULS[mm_name]("sd,dv->sv", h_rows, params["head"])
'''

BIGRAM = {"name": "bigram", "reference": "toy_bigram", "hidden_size": 8,
          "vocab_size": 32}


@pytest.fixture
def bigram(monkeypatch, tmp_path):
    """The toy architecture's two modules, in directories added to the
    ``harness.arch`` and ``reference`` packages for this test only."""
    import reference
    for pkg, source in ((arch, HARNESS_SIDE), (reference, REFERENCE_SIDE)):
        d = tmp_path / pkg.__name__
        d.mkdir()
        (d / "toy_bigram.py").write_text(source)
        monkeypatch.setattr(pkg, "__path__", [*pkg.__path__, str(d)])
    yield BIGRAM
    for name in ("harness.arch.toy_bigram", "reference.toy_bigram"):
        sys.modules.pop(name, None)


def test_a_new_architecture_is_new_files(bigram):
    assert arch.known() == ["dense_gqa", "toy_bigram"]
    assert spec.model_config(bigram) == ("bigram", "bigram")
    n = 2 * 32 * 8
    assert counts.n_params(bigram) == n
    assert counts.adamw_bytes(bigram) == 18 * n
    assert counts.layer_matmul_params(bigram) == 0
    assert counts.attention_flops(bigram, 5) == 0
    # forward 2 x D x V a token (= n), backward twice that
    assert counts.train_flops_per_step(bigram, 2, 4) == 3 * 2 * 4 * n

    shapes = {"embed": jax.ShapeDtypeStruct((32, 8), jnp.bfloat16),
              "head": jax.ShapeDtypeStruct((8, 32), jnp.bfloat16)}
    w0 = weights.make(shapes, seeds.key(3, "weights"))
    make = train.batch_fn(bigram, {"batch": 2, "seq": 16}, 3)
    batches = [make(i) for i in range(2)]
    opt = toy._json("traffic", "train.4k.json")["optimizer"]
    out = ref_train.run(bigram, w0, batches, opt, steps=2,
                        devices=jax.devices()[:1])
    plain = sys.modules["reference.toy_bigram"]
    w32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w0)
    want = [float(plain.loss(bigram, w32, b["tokens"], b["labels"]))
            for b in batches]
    # the change after step 0 (lr 0) is 2e-8 of a step: the losses agree
    np.testing.assert_allclose(out["losses"], want, rtol=1e-6)
    assert len(out["grad_norms"]) == 2 and np.all(out["grad_norms"] > 0)
    assert np.all(out["change_norms"] > 0)


@pytest.mark.parametrize("name", ["moe_nowhere", "train", None])
@pytest.mark.parametrize("entry", ["load_cell", "model_config", "counts",
                                   "reference"])
def test_unknown_architecture_names_the_known(entry, name, tmp_path):
    cfg = dict(toy.TINY, reference=name)
    if name is None:
        del cfg["reference"]
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(cfg))
    bench = {"configs": [{"name": "tiny", "file": str(path)}],
             "workloads": [{"name": "toy", "config": "tiny",
                            "traffic": "train.4k", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    call = {"load_cell": lambda: spec.load_cell("toy", bench),
            "model_config": lambda: spec.model_config(cfg),
            "counts": lambda: counts.train_flops_per_step(cfg, 1, 4),
            "reference": lambda: ref_train.run(
                cfg, {}, [], {}, steps=1, devices=jax.devices()[:1])}[entry]
    with pytest.raises(ValueError, match="known: dense_gqa"):
        call()
