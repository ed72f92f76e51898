"""Per-layer bucketed compressed reduction: bucket-plan invariants, codec
numerics on the real reduction path (int8 AND topk), the per-pod residual
regression (out_spec P() used to collapse the error-feedback accumulators
on pod>1 meshes), and a ≥2-pod host-mesh equivalence run.

The multi-pod tests run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` because the jax
device count locks at first init and the in-process suite must see the
real single CPU device (see conftest).
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.dist import compression
from repro.dist.compression import (BLOCK, bucketed_compressed_psum,
                                    init_residuals, plan_buckets,
                                    quantize_with_feedback, topk_psum)
from repro.models import build_model
from repro.models.spec import init_params, is_spec
from repro.train.optimizer import AdamWConfig
from repro.train.step import grad_bucket_plan, make_train_step

# ---------------------------------------------------------------- bucket plan


def test_plan_buckets_partitions_every_leaf_in_order():
    sizes = [512, 32, 256, 8, 4096, 16, 16]
    plan = plan_buckets(sizes, bucket_elems=600)
    flat = [i for g in plan.groups for i in g]
    assert flat == list(range(len(sizes))), "every leaf, original order"
    for g, size, padded in zip(plan.groups, plan.sizes, plan.padded_sizes):
        assert size == sum(sizes[i] for i in g)
        assert padded % BLOCK == 0 and 0 <= padded - size < BLOCK
        # size cap respected unless a single oversized leaf owns the bucket
        assert size <= 600 or len(g) == 1


def test_plan_buckets_single_bucket_when_cap_is_huge():
    plan = plan_buckets([100, 200, 300], bucket_elems=1 << 30)
    assert plan.num_buckets == 1 and plan.sizes == (600,)


def test_plan_buckets_matches_model_leaf_count():
    api = build_model(get_config("qwen2-1.5b", smoke=True))
    plan = grad_bucket_plan(api, bucket_elems=1 << 14)
    assert plan.num_buckets > 1, "smoke model must split at this cap"
    n_leaves = sum(len(g) for g in plan.groups)
    assert n_leaves == len(jax.tree.leaves(api.init_specs(), is_leaf=is_spec))


# --------------------------------------------------- codec numerics (1 pod)


def _toy_tree(seed=0):
    rng = np.random.default_rng(seed)
    shapes = [(16, 32), (32,), (32, 8), (8,)]
    return [jnp.asarray(rng.standard_normal(s), jnp.float32) for s in shapes]


def _pod1_reduce(tree, plan, codec):
    """bucketed_compressed_psum inside a real (1-sized) pod manual region —
    the identical code path the train step runs."""
    mesh = jax.make_mesh((1,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    errs = init_residuals(plan, pod_size=1)

    def fn(tree, errs):
        return bucketed_compressed_psum(tree, errs, "pod", plan=plan,
                                        codec=codec, topk_frac=0.25)

    sm = jax.shard_map(fn, mesh=mesh, in_specs=(P(), P("pod")),
                       out_specs=(P(), P("pod")), axis_names={"pod"},
                       check_vma=False)
    with jax.set_mesh(mesh):
        return sm(tree, errs)


@pytest.mark.parametrize("codec", ["int8", "topk"])
@pytest.mark.parametrize("bucket_elems", [300, 1 << 20])
def test_bucketed_reduction_within_error_feedback_bound(codec, bucket_elems):
    """On a 1-pod mesh psum is the identity, so reduced + residual must
    telescope back to the input exactly (topk) / within f32 rounding
    (int8), and |reduced - input| must respect the codec's bound."""
    tree = _toy_tree()
    sizes = [int(t.size) for t in tree]
    plan = plan_buckets(sizes, bucket_elems=bucket_elems)
    reduced, new_errs = _pod1_reduce(tree, plan, codec)
    for b, group in enumerate(plan.groups):
        flat = jnp.concatenate([jnp.ravel(tree[i]) for i in group])
        flat = jnp.pad(flat, (0, plan.padded_sizes[b] - plan.sizes[b]))
        red = jnp.concatenate([jnp.ravel(reduced[i]) for i in group])
        red = jnp.pad(red, (0, plan.padded_sizes[b] - plan.sizes[b]))
        # telescoping identity: reduced + residual == input
        np.testing.assert_allclose(np.asarray(red + new_errs[b]),
                                   np.asarray(flat), atol=1e-5, rtol=0)
        # codec error bound on |reduced - plain psum|
        if codec == "int8":
            blocks = jnp.abs(flat.reshape(-1, BLOCK))
            scale = jnp.max(blocks, axis=1, keepdims=True) / 127.0
            bound = jnp.repeat(scale[:, 0] / 2.0, BLOCK) + 1e-6
        else:
            k = max(1, int(round(0.25 * flat.shape[0])))
            tau = jax.lax.top_k(jnp.abs(flat), k)[0][-1]
            bound = jnp.full_like(flat, tau) + 1e-6
        assert np.all(np.abs(np.asarray(red - flat)) <= np.asarray(bound)), \
            f"bucket {b} exceeds the {codec} error bound"


def test_bucketed_reduction_agrees_across_bucket_sizes():
    """Regrouping leaves into different buckets shifts the 256-element
    quantization block boundaries, so results are not bit-identical — but
    every grouping stays within one blockwise quantization step of every
    other (each is within scale/2 of the true value)."""
    tree = _toy_tree()
    sizes = [int(t.size) for t in tree]
    outs = []
    for bucket_elems in (300, 600, 1 << 20):
        plan = plan_buckets(sizes, bucket_elems=bucket_elems)
        reduced, _ = _pod1_reduce(tree, plan, "int8")
        outs.append(np.concatenate([np.ravel(r) for r in reduced]))
    scale_bound = max(float(jnp.max(jnp.abs(t))) for t in tree) / 127.0
    np.testing.assert_allclose(outs[0], outs[1], atol=scale_bound + 1e-6)
    np.testing.assert_allclose(outs[0], outs[2], atol=scale_bound + 1e-6)


# ------------------------------------- per-pod residual telescoping (numpy)


def test_per_pod_residuals_telescope_and_collapsed_residuals_do_not():
    """Multi-step, multi-pod codec simulation: with each pod carrying its
    own residual the summed applied updates telescope to the true gradient
    sum minus the final mean residual (bounded); force-collapsing the
    residuals to pod 0's copy each step (the PR-1 out_spec P() bug) breaks
    the guarantee by orders of magnitude."""
    pods, steps, n = 4, 6, 512
    rng = np.random.default_rng(7)
    grads = rng.standard_normal((steps, pods, n)).astype(np.float32)

    def run(collapse):
        errs = [jnp.zeros((n,), jnp.float32) for _ in range(pods)]
        applied = jnp.zeros((n,), jnp.float32)
        for t in range(steps):
            deqs = []
            for p in range(pods):
                q, scale, pad, new_err = quantize_with_feedback(
                    jnp.asarray(grads[t, p]), errs[p])
                deqs.append(compression.dequantize_int8(q, scale, pad,
                                                        (n,)))
                errs[p] = new_err
            if collapse:
                errs = [errs[0]] * pods
            applied = applied + sum(deqs) / pods
        return np.asarray(applied), np.stack([np.asarray(e) for e in errs])

    true_sum = grads.mean(axis=1).sum(axis=0)   # mean over pods, sum steps

    applied, errs = run(collapse=False)
    # telescoping: applied == true_sum - mean_p(final residual)
    residual_term = errs.mean(axis=0)
    np.testing.assert_allclose(applied + residual_term, true_sum, atol=1e-4)
    # the final residual itself is bounded by one quantization step
    assert np.abs(residual_term).max() < 0.1

    applied_c, errs_c = run(collapse=True)
    drift_ok = np.abs(applied + errs.mean(axis=0) - true_sum).max()
    drift_bad = np.abs(applied_c + errs_c.mean(axis=0) - true_sum).max()
    assert drift_bad > 50 * drift_ok, \
        "collapsing per-pod residuals must visibly break telescoping"


# ----------------------------------------- train-step residual state (1 pod)


def test_train_step_residuals_sharded_per_pod_and_carried():
    cfg = get_config("qwen2-1.5b", smoke=True)
    api = build_model(cfg)
    mesh = jax.make_mesh((1, 1, 1), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    step, _, bsh, init_state = make_train_step(
        api, mesh, AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=4),
        compress_pod_grads=True, bucket_elems=1 << 14)
    plan = grad_bucket_plan(api, bucket_elems=1 << 14)
    batch = {"tokens": jnp.ones((4, 16), jnp.int32),
             "targets": jnp.ones((4, 16), jnp.int32)}
    with jax.set_mesh(mesh):
        params = init_params(api.init_specs(), jax.random.PRNGKey(0))
        state = init_state(params)
        assert isinstance(state["err"], list)
        assert len(state["err"]) == plan.num_buckets > 1
        for e, padded in zip(state["err"], plan.padded_sizes):
            assert e.shape == (padded,)               # pod size 1
            assert e.sharding.spec == P("pod"), \
                "residuals must shard over the pod axis, not collapse"
        b = jax.device_put(batch, bsh)
        state, _ = step(state, b)
        state, _ = step(state, b)
    assert any(float(jnp.abs(e).max()) > 0 for e in state["err"]), \
        "error feedback must actually carry a residual"


# ------------------------------------------------ >= 2-pod host mesh (subproc)

_MULTIPOD_SCRIPT = textwrap.dedent("""
    import json, sys
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.dist import compression
    from repro.dist.compression import (
        BLOCK, bucketed_compressed_psum, init_residuals, plan_buckets)

    assert len(jax.devices()) >= 2, jax.devices()
    PODS = 2
    mesh = jax.make_mesh((PODS,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))

    # -- toy multi-layer model, hand-rolled training loop ------------------
    rng = np.random.default_rng(0)
    shapes = [(16, 32), (32,), (32, 8), (8,)]
    params0 = [jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)
               for s in shapes]
    xs = jnp.asarray(rng.standard_normal((PODS, 64, 16)), jnp.float32)
    ys = jnp.asarray(rng.standard_normal((PODS, 64, 8)), jnp.float32)

    def predict(params, x):
        w1, b1, w2, b2 = params
        return jnp.tanh(x @ w1 + b1) @ w2 + b2

    def loss_fn(params, x, y):
        return jnp.mean((predict(params, x) - y) ** 2)

    sizes = [int(np.prod(s)) for s in shapes]
    plan = plan_buckets(sizes, bucket_elems=600)   # forces 2 buckets
    assert plan.num_buckets == 2
    LR, STEPS, FRAC = 0.05, 12, 0.25

    def make_step(codec):
        def stepfn(params, errs, x, y):
            g = jax.grad(loss_fn)(params, x, y)
            viol = jnp.zeros(())
            if codec == "none":
                g = jax.tree.map(lambda a: jax.lax.pmean(a, "pod"), g)
            else:
                leaves = jax.tree.leaves(g)
                red, new_errs = bucketed_compressed_psum(
                    g, errs, "pod", plan=plan, codec=codec, topk_frac=FRAC)
                # per-step acceptance check: |compressed psum - plain psum|
                # within the codec's error-feedback bound
                for b, group in enumerate(plan.groups):
                    flat = jnp.concatenate(
                        [jnp.ravel(leaves[i]) for i in group])
                    flat = jnp.pad(
                        flat, (0, plan.padded_sizes[b] - plan.sizes[b]))
                    x_b = flat + errs[b]
                    plain = jax.lax.pmean(x_b, "pod")
                    red_b = jnp.concatenate(
                        [jnp.ravel(jax.tree.leaves(red)[i]) for i in group])
                    red_b = jnp.pad(
                        red_b, (0, plan.padded_sizes[b] - plan.sizes[b]))
                    if codec == "int8":
                        blocks = jnp.abs(x_b.reshape(-1, BLOCK))
                        scale = jnp.max(blocks, axis=1, keepdims=True) / 127.0
                        bound = jnp.repeat(scale[:, 0] / 2.0, BLOCK)
                    else:
                        k = max(1, int(round(FRAC * x_b.shape[0])))
                        tau = jax.lax.top_k(jnp.abs(x_b), k)[0][-1]
                        bound = jnp.full_like(x_b, tau)
                    bound = jax.lax.pmean(bound, "pod") + 1e-6
                    viol = jnp.maximum(
                        viol, jnp.max(jnp.abs(red_b - plain) - bound))
                g, errs = red, new_errs
            params = jax.tree.map(lambda p, a: p - LR * a, params, g)
            loss = jax.lax.pmean(loss_fn(params, x, y), "pod")
            return params, errs, loss, viol

        return jax.jit(jax.shard_map(
            stepfn, mesh=mesh,
            in_specs=(P(), P("pod"), P("pod"), P("pod")),
            out_specs=(P(), P("pod"), P(), P()),
            axis_names={"pod"}, check_vma=False))

    def run(codec):
        fn = make_step(codec)
        params = list(params0)
        errs = init_residuals(plan, pod_size=PODS)
        losses, max_viol = [], 0.0
        for _ in range(STEPS):
            params, errs, loss, viol = fn(params, errs, xs, ys)
            losses.append(float(loss))
            max_viol = max(max_viol, float(viol))
        halves = [np.asarray(e).reshape(PODS, -1) for e in errs]
        return params, {
            "losses": losses, "max_bound_violation": max_viol,
            "residual_pods_differ": bool(any(
                not np.array_equal(h[0], h[1]) for h in halves)),
            "err_global_shapes": [list(np.asarray(e).shape) for e in errs],
        }

    out = {}
    ref_params, out["none"] = run("none")
    for codec in ("int8", "topk"):
        p, rec = run(codec)
        rec["max_param_drift_vs_uncompressed"] = max(
            float(jnp.max(jnp.abs(a - b))) for a, b in zip(p, ref_params))
        out[codec] = rec

    # -- the real train step on a (pod=2, data=1, model=1) mesh ------------
    from repro.configs import get_config
    from repro.models import build_model
    from repro.models.spec import init_params
    from repro.train.optimizer import AdamWConfig
    from repro.train.step import make_train_step

    cfg = get_config("qwen2-1.5b", smoke=True)
    api = build_model(cfg)
    tmesh = jax.make_mesh((2, 1, 1), ("pod", "data", "model"),
                          axis_types=(jax.sharding.AxisType.Auto,) * 3)
    # per-row distinct tokens: the batch shards over "pod" on dim 0, so the
    # two pods see different data and must accumulate different residuals
    toks = np.random.default_rng(3).integers(0, cfg.vocab, (4, 17))
    batch = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
             "targets": jnp.asarray(toks[:, 1:], jnp.int32)}
    train = {}
    for codec in ("none", "int8", "topk"):
        step, _, bsh, init_state = make_train_step(
            api, tmesh, AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=5),
            compress_pod_grads=codec != "none",
            codec=codec if codec != "none" else "int8",
            bucket_elems=1 << 14)
        with jax.set_mesh(tmesh):
            params = init_params(api.init_specs(), jax.random.PRNGKey(2))
            state = init_state(params)
            b = jax.device_put(batch, bsh)
            ls = []
            for _ in range(4):
                state, m = step(state, b)
                ls.append(float(m["loss"]))
        rec = {"losses": ls}
        if codec != "none":
            halves = [np.asarray(e).reshape(2, -1) for e in state["err"]]
            rec["residual_pods_differ"] = bool(any(
                not np.array_equal(h[0], h[1]) for h in halves))
        train[codec] = rec
    out["train"] = train
    print("RESULT " + json.dumps(out))
""")


def test_multipod_bucketed_psum_matches_plain_within_bound():
    """Acceptance gate: on a 2-pod host mesh, per-layer bucketed
    compressed_psum (int8 AND topk) matches uncompressed psum within the
    error-feedback bound over a multi-step training loop, residuals stay
    per-pod, and the real train step's trajectory tracks uncompressed."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=2").strip()
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run([sys.executable, "-c", _MULTIPOD_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=900)
    assert proc.returncode == 0, f"subprocess failed:\n{proc.stderr[-4000:]}"
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])

    for codec in ("int8", "topk"):
        rec = out[codec]
        assert rec["max_bound_violation"] <= 0.0, \
            f"{codec}: compressed psum left the error-feedback bound"
        assert rec["residual_pods_differ"], \
            f"{codec}: per-pod residuals collapsed (regression)"
        assert rec["losses"][-1] < rec["losses"][0], f"{codec} diverged"
        # padded global residual rows: one per pod
        for shape in rec["err_global_shapes"]:
            assert shape[0] % 2 == 0
    # int8 quantization is fine-grained: the whole trajectory stays close
    np.testing.assert_allclose(out["int8"]["losses"], out["none"]["losses"],
                               rtol=0.05)
    assert out["int8"]["max_param_drift_vs_uncompressed"] < 0.05
    # topk drops 75% of entries; error feedback still recovers convergence
    assert out["topk"]["losses"][-1] < out["none"]["losses"][0]

    train = out["train"]
    np.testing.assert_allclose(train["int8"]["losses"],
                               train["none"]["losses"], rtol=0.05)
    assert train["topk"]["losses"][-1] < train["topk"]["losses"][0]
    assert train["int8"]["residual_pods_differ"]
    assert train["topk"]["residual_pods_differ"]
