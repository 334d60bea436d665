"""Architecture modules, on the CPU: the harness finds a configuration's
``bench/arch/<architectures[0]>.py`` or names the path it looked for;
Qwen3's weights come out bit-identical to those drawn before its module
was split out of the harness; and a new architecture needs only new
files: a test-only one, written into a copy of the benchmark, runs
``correct`` through the driver with no file of the copy written; and a
test-only architecture with two kinds of layer, also new files only,
passes through the weights, the reference and the per-layer naming."""
import functools
import hashlib
import json
import pathlib
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import benchtiny  # noqa: E402

SEED = 2 ** 31 + 7

# sha256 over every leaf's name, dtype, shape and bytes, in name order, of
# the trees the harness drew at ``benchtiny.TINY`` sizes from SEED before
# the architecture modules existed
WEIGHT_DIGESTS = {
    "make_tied": "f685b109550ad33b73527adcb7274bff7988ac3c76c90101e090ba4b702c3a6c",
    "make_untied": "0c1ecad568267644154db4d765ce084c140adb6ab2e85e555efa030b6cf909a6",
    "top_untied": "bc57e57ab197770c504711e9390188ba4439e9590410881687a7f35e1cd042ef",
    "layers": "92d97348b483d4a04933e10d3c5ad8fefb29e568765f7fac5a82eacdb3ae1fdb",
}

# Qwen3's dense block without the RMSNorm on each head's q and k; the rest
# is taken from Qwen3's module, as a new architecture may take it
NOQK_ARCH = '''"""Test-only: Qwen3's dense block without the q/k RMSNorm."""
import dataclasses
import pathlib

import jax

from benchlib import reference as R
from benchlib.harness import load_module

qwen3 = load_module(pathlib.Path(__file__).with_name("Qwen3ForCausalLM.py"),
                    "bench_arch_qwen3_base")
dims, top_specs = qwen3.dims, qwen3.top_specs


def program_config(c):
    return dataclasses.replace(qwen3.program_config(c), qk_norm=False)


def layer_specs(d):
    return [s for s in qwen3.layer_specs(d)
            if s[0] not in (("attn", "q_norm"), ("attn", "k_norm"))]


def block(w, x, dims, i, theta, eps, cast=None):
    b, t, _ = x.shape
    hd, kv = dims.head_dim, dims.kv_heads
    h = R.rmsnorm(x, w["ln1"]["scale"], eps)
    a = w["attn"]
    q = R.rope(R.mm(h, a["wq"], cast).reshape(b, t, dims.heads, hd), theta)
    k = R.rope(R.mm(h, a["wk"], cast).reshape(b, t, kv, hd), theta)
    v = R.mm(h, a["wv"], cast).reshape(b, t, kv, hd)
    x = x + R.mm(R.attention(q, k, v, cast), a["wo"], cast)
    h = R.rmsnorm(x, w["ln2"]["scale"], eps)
    m = w["mlp"]
    y = jax.nn.silu(R.mm(h, m["wi_gate"], cast)) * R.mm(h, m["wi_up"], cast)
    return x + R.mm(y, m["wo"], cast), R.NO_EXTRA_LOSS
'''


def _digest(tree, per_layer_leaves, roots) -> str:
    h = hashlib.sha256()
    for name, a in sorted(per_layer_leaves(tree, roots).items()):
        a = np.asarray(a)
        h.update(f"{name}:{a.dtype}:{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _tiny_qwen3():
    h = benchtiny.harness(benchtiny.REPO)
    cfg = json.loads((benchtiny.REPO / "bench/configs/qwen3-4b.json").read_text())
    cfg.update(benchtiny.TINY)
    return h, cfg, h.load_arch(cfg)


@pytest.mark.parametrize("tree", sorted(WEIGHT_DIGESTS))
def test_qwen3_weights_are_those_drawn_before_the_move(tree):
    _, cfg, arch = _tiny_qwen3()
    from benchlib import weights as W
    d = arch.dims(cfg)
    made = {
        "make_tied": lambda: W.make(arch, d, True, SEED),
        "make_untied": lambda: W.make(arch, d, False, SEED),
        "top_untied": lambda: W.top(arch, d, False, SEED),
        "layers": lambda: {f"l{i}": W.layer(arch, d, SEED, i)
                           for i in range(d.layers)},
    }[tree]()
    assert W.roots(arch, d) == ("blocks",)
    assert _digest(made, W.per_layer_leaves, W.roots(arch, d)) == WEIGHT_DIGESTS[tree]


def test_qwen3_block_adds_no_extra_loss():
    _, cfg, arch = _tiny_qwen3()
    from benchlib import weights as W
    d = arch.dims(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, d.d_model))
    y, extra = arch.block(W.layer(arch, d, SEED, 1), x, d, np.int32(1),
                          1e6, 1e-6)
    assert y.shape == x.shape and np.asarray(extra) == np.float32(0.0)
    assert np.asarray(extra).dtype == np.float32


def test_missing_architecture_module_names_its_path(tmp_path):
    h = benchtiny.harness(benchtiny.REPO)
    with pytest.raises(FileNotFoundError,
                       match="bench/arch/NoSuchForCausalLM.py"):
        h.load_arch({"name": "x", "architectures": ["NoSuchForCausalLM"]},
                    root=tmp_path)


def _snapshot(root: pathlib.Path) -> dict:
    return {p: (p.stat().st_mtime_ns, p.read_bytes())
            for p in root.rglob("*") if p.is_file() and "__pycache__" not in p.parts}


def _with_qk_norm(arch):
    """The test-only architecture with Qwen3's RMSNorm of each head's q and
    k put back at unit scale: the block it must not be mistaken for."""
    def block(w, x, *args, **kw):
        return arch.qwen3.block(dict(w, attn=dict(w["attn"], q_norm=1.0,
                                             k_norm=1.0)), x, *args, **kw)

    mod = types.ModuleType("with_qk_norm")
    mod.__dict__.update(dims=arch.dims, layer_specs=arch.layer_specs,
                        top_specs=arch.top_specs, block=block)
    return mod


def test_new_architecture_needs_only_new_files(tmp_path, monkeypatch):
    root = benchtiny.make_root(tmp_path)
    # the new files: an architecture module, a configuration naming it,
    # and its cell under the tiny mix
    (root / "bench/arch/NoQKNormTestForCausalLM.py").write_text(NOQK_ARCH)
    cfg = json.loads((root / "bench/configs/tiny.json").read_text())
    cfg.update(name="tiny-noqk", architectures=["NoQKNormTestForCausalLM"])
    (root / "bench/configs/tiny-noqk.json").write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny-noqk", "config": "tiny-noqk",
                               "traffic": "tiny_rl", "chips": 1,
                               "why": "CPU test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-rl" in m.get("workloads", []):
            m["workloads"].append("tiny-noqk")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    # the harness as it stands: every file copied from the repository
    for p in (benchtiny.REPO / "bench").rglob("*"):
        if p.is_file() and "__pycache__" not in p.parts:
            assert (root / p.relative_to(benchtiny.REPO)).read_bytes() == p.read_bytes()
    before = _snapshot(root)

    benchtiny.harness(root)
    from benchlib import check, reference
    served = []
    check_served = check.served

    def recording(cfg, arch, seed, samples, limits, cast=None):
        served.append((cfg, arch, seed, samples))
        return check_served(cfg, arch, seed, samples, limits, cast)

    monkeypatch.setattr(check, "served", recording)
    out, line = benchtiny.run(root, "noqk")

    # 1: the run is correct, through the driver as it stands
    assert line["correct"], line["checks"]
    assert out.attempted > 0 and out.failed == 0
    (cfg, arch, seed, samples), = served
    assert arch.__file__.endswith("bench/arch/NoQKNormTestForCausalLM.py")
    assert not any(p[1] in ("q_norm", "k_norm")
                   for p, *_ in arch.layer_specs(arch.dims(cfg)))

    # 2: the negative control: the reference with q/k norms put back, on
    # the same served sequences and weights, is far from this one's
    seqs = [np.concatenate([np.asarray(p, np.int32).ravel(),
                            np.asarray(r, np.int32).ravel()])
            for p, r, _ in samples]
    lp = functools.partial(reference.served_logprobs, cfg, seed=seed,
                           seqs=seqs, rows=len(seqs))
    ref = lp(arch)
    alt = lp(_with_qk_norm(arch))
    gap = max(float(np.max(np.abs(a[len(np.asarray(p).ravel()):]
                                  - b[len(np.asarray(p).ravel()):])))
              for a, b, (p, _, _) in zip(ref, alt, samples, strict=True))
    limit = line["checks"]["served_logprob_gap"]["limit"]
    assert limit == 0.1 and gap > limit, gap

    # 3: no file the copy started with was written
    after = _snapshot(root)
    assert {p: v for p, v in after.items() if p in before} == before


# two kinds of layer: layer 0 with an MLP of its own width under the root
# ``dense``, layers 1-3 under ``blocks`` with an extra loss that grows with
# the layer's index over all the layers
TWO_KIND_ARCH = '''"""Test-only: a leading layer with an MLP of its own width, then
Qwen3's blocks, each adding an extra loss scaled by its layer index."""
import dataclasses
import pathlib

import jax.numpy as jnp

from benchlib.harness import load_module
from benchlib.weights import LayerKind

qwen3 = load_module(pathlib.Path(__file__).with_name("Qwen3ForCausalLM.py"),
                    "bench_arch_qwen3_base")
dims, top_specs, program_config = qwen3.dims, qwen3.top_specs, qwen3.program_config
FIRST_FF = 96


def later_block(w, x, dims, i, theta, eps, cast=None):
    y, _ = qwen3.block(w, x, dims, i, theta, eps, cast)
    return y, 1e-3 * (i + 1) * jnp.mean(y * y)


def layer_kinds(d):
    first = dataclasses.replace(d, d_ff=FIRST_FF)
    return [LayerKind("dense", 1, tuple(qwen3.layer_specs(first)), qwen3.block),
            LayerKind("blocks", d.layers - 1, tuple(qwen3.layer_specs(d)),
                      later_block)]
'''

# (root, index within the stack) of each layer of the two-kind architecture
TWO_KIND_LAYERS = [("dense", 0), ("blocks", 0), ("blocks", 1), ("blocks", 2)]


@pytest.fixture(scope="module")
def two_kind(tmp_path_factory):
    """The two-kind architecture and its configuration, new files in a
    copy of the benchmark whose every other file is the repository's."""
    root = benchtiny.make_root(tmp_path_factory.mktemp("twokind"))
    (root / "bench/arch/TwoKindTestForCausalLM.py").write_text(TWO_KIND_ARCH)
    cfg = json.loads((root / "bench/configs/tiny.json").read_text())
    cfg.update(name="tiny-twokind", architectures=["TwoKindTestForCausalLM"],
               num_hidden_layers=len(TWO_KIND_LAYERS))
    (root / "bench/configs/tiny-twokind.json").write_text(json.dumps(cfg))
    for p in (benchtiny.REPO / "bench").rglob("*"):
        if p.is_file() and "__pycache__" not in p.parts:
            assert (root / p.relative_to(benchtiny.REPO)).read_bytes() == p.read_bytes()
    h = benchtiny.harness(root)
    return cfg, h.load_arch(cfg, root)


def test_two_kinds_stack_under_their_roots_and_each_layer_alone_is_its_slice(
        two_kind):
    cfg, arch = two_kind
    from benchlib import weights as W
    d = arch.dims(cfg)
    assert W.roots(arch, d) == ("dense", "blocks")
    tree = W.make(arch, d, True, SEED)
    assert set(tree) == {"embed", "final_norm", "dense", "blocks"}
    assert tree["dense"]["mlp"]["wo"].shape == (1, 96, 64)
    assert tree["blocks"]["mlp"]["wo"].shape == (3, 128, 64)
    for i, (root, j) in enumerate(TWO_KIND_LAYERS):
        alone = W.layer(arch, d, SEED, i)
        sliced = jax.tree_util.tree_map(lambda a: a[j].astype(jnp.float32),
                                        tree[root])
        assert jax.tree_util.tree_structure(alone) == \
            jax.tree_util.tree_structure(sliced)
        for a, b in zip(jax.tree_util.tree_leaves(alone),
                        jax.tree_util.tree_leaves(sliced), strict=True):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (i, root)
    with pytest.raises(IndexError):
        W.layer(arch, d, SEED, len(TWO_KIND_LAYERS))
    # the per-layer names of the training comparison cover both stacks
    names = set(W.per_layer_leaves(tree, W.roots(arch, d)))
    assert {"dense/mlp/wo[0]", "blocks/mlp/wo[0]", "blocks/mlp/wo[2]",
            "embed", "final_norm/scale"} <= names
    assert not {"dense/mlp/wo[1]", "blocks/mlp/wo[3]", "dense/mlp/wo",
                "blocks/mlp/wo"} & names


def _tokens(d, shape=(2, 12)):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(3), shape, 0,
                                         d.vocab), np.int32)


def test_two_kinds_forward_is_the_layers_in_order(two_kind):
    cfg, arch = two_kind
    from benchlib import reference as R
    from benchlib import weights as W
    d = arch.dims(cfg)
    theta, eps = float(cfg["rope_theta"]), float(cfg["rms_norm_eps"])
    tokens = _tokens(d)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    W.make(arch, d, True, SEED))
    lp, extra = jax.jit(lambda p, t: R.forward_logprobs(p, t, cfg, arch))(
        params, tokens)
    # by hand: each layer alone, through the block its kind names, at its
    # index over all the layers
    top = W.top(arch, d, True, SEED)
    blocks = [arch.qwen3.block] + [arch.later_block] * 3
    with jax.default_matmul_precision("highest"):
        x = jnp.take(top["embed"], tokens, axis=0)
        total = 0.0
        for i, block in enumerate(blocks):
            x, e = block(W.layer(arch, d, SEED, i), x, d, np.int32(i), theta, eps)
            total += float(e)
        hand = R.token_logprobs(R.rmsnorm(x, top["final_norm"]["scale"], eps),
                                R.head_matrix(top, True), tokens)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(hand),
                               rtol=1e-5, atol=1e-5)
    assert total > 0 and float(extra) == pytest.approx(total, rel=1e-5)
    served = R.served_logprobs(cfg, arch, SEED, list(tokens), rows=2)
    np.testing.assert_allclose(np.stack(served), np.asarray(hand),
                               rtol=1e-5, atol=1e-5)


def test_two_kinds_first_gradient_reaches_both_stacks(two_kind):
    cfg, arch = two_kind
    from benchlib import reference as R
    from benchlib import weights as W
    d = arch.dims(cfg)
    mix = json.loads((benchtiny.REPO / "bench/traffic/rlvr_grpo8_short.json")
                     .read_text())
    tokens = _tokens(d, (4, 12))
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    W.make(arch, d, True, SEED))
    lp, _ = R.forward_logprobs(params, tokens, cfg, arch)
    mask = np.zeros(tokens.shape, np.float32)
    mask[:, 4:] = 1.0
    adv = np.asarray([1.0, -1.0, 0.5, -0.5], np.float32)[:, None] * mask
    feed = {"tokens": tokens, "mask": mask, "advantages": adv,
            "old_logprobs": np.asarray(lp) * mask}
    grads = R.train_steps(cfg, arch, SEED, [feed], mix["loss"],
                          mix["optimizer"])["grad_norm"]
    stacked = {n: g for n, g in grads.items()
               if n.startswith(("dense/", "blocks/"))}
    assert {n.split("/")[0] for n in stacked} == {"dense", "blocks"}
    assert len(stacked) == 11 * len(TWO_KIND_LAYERS)
    assert all(g > 0 for g in stacked.values()), stacked


def test_driver_leaf_norms_split_every_stack_as_the_reference_does(two_kind):
    cfg, arch = two_kind
    from benchlib import reference as R
    from benchlib import weights as W
    from benchlib.harness import load_module
    rl = load_module(benchtiny.REPO / "bench/drivers/rl.py", "bench_driver_rl")
    d = arch.dims(cfg)
    tree = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                  W.make(arch, d, False, SEED))
    roots = W.roots(arch, d)
    prog, ref = rl.leaf_norms(tree, roots), R.leaf_norms(tree, roots)
    assert set(prog) == set(ref)
    assert "dense/mlp/wi_up[0]" in prog and "blocks/attn/wq[2]" in prog
    for n, v in ref.items():
        assert prog[n] == pytest.approx(v, rel=1e-5), n
