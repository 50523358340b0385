"""Sharding rule resolution (hypothesis properties) + HLO analyzer units +
multi-device subprocess integration (mini dry-run, compressed grads)."""

import hypothesis
import hypothesis.strategies as st
import numpy as np
import pytest

from repro.core.hlo_analysis import (axes_for_groups, parse_replica_groups,
                                     shape_bytes)


# ----------------------------------------------------------- hlo parsing


def test_shape_bytes():
    assert shape_bytes("f32[4,4]") == 64
    assert shape_bytes("bf16[2,3]{1,0}") == 12
    assert shape_bytes("(s32[], f32[10], bf16[4])") == 4 + 40 + 8
    assert shape_bytes("pred[]") == 1
    assert shape_bytes("f8e4m3fn[100]") == 100


def test_parse_replica_groups_list_format():
    groups = parse_replica_groups("replica_groups={{0,1},{2,3}}, x=y")
    assert groups == ((0, 1), (2, 3))


def test_parse_replica_groups_iota_format():
    groups = parse_replica_groups(
        "replica_groups=[2,4]<=[4,2]T(1,0), use_global_device_ids=true")
    assert groups == ((0, 2, 4, 6), (1, 3, 5, 7))
    groups = parse_replica_groups("replica_groups=[4,2]<=[8]")
    assert groups == ((0, 1), (2, 3), (4, 5), (6, 7))


def test_axes_for_groups():
    # mesh (4, 2) ("data", "model"), row-major ids
    model_groups = ((0, 1), (2, 3), (4, 5), (6, 7))
    assert axes_for_groups(model_groups, (4, 2), ("data", "model")) == \
        ("model",)
    data_groups = ((0, 2, 4, 6), (1, 3, 5, 7))
    assert axes_for_groups(data_groups, (4, 2), ("data", "model")) == \
        ("data",)
    all_groups = ((0, 1, 2, 3, 4, 5, 6, 7),)
    assert set(axes_for_groups(all_groups, (4, 2), ("data", "model"))) == \
        {"data", "model"}


def test_trip_count_scaling(subproc):
    """Analyzer scales while-body costs by known_trip_count (the core fix
    over XLA cost_analysis)."""
    out = subproc("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core.hlo_analysis import analyze_compiled_text
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
S = lambda *s: NamedSharding(mesh, P(*s))
def make(L):
    def step(ws, x):
        def body(x, w):
            return jax.lax.with_sharding_constraint(x @ w, S("data", None)), None
        out, _ = jax.lax.scan(body, x, ws)
        return jnp.sum(out.astype(jnp.float32)**2)
    ws = jax.ShapeDtypeStruct((L, 64, 64), jnp.bfloat16)
    xs = jax.ShapeDtypeStruct((32, 64), jnp.bfloat16)
    f = jax.jit(jax.grad(step), in_shardings=(S(None,None,"model"), S("data",None)),
                out_shardings=S(None,None,"model"))
    txt = f.lower(ws, xs).compile().as_text()
    return analyze_compiled_text(txt, (4,2), ("data","model"))
r5, r10 = make(5), make(10)
assert 1.9 < r10.flops / r5.flops < 2.1, (r5.flops, r10.flops)
c5 = sum(c.multiplier for c in r5.collectives)
c10 = sum(c.multiplier for c in r10.collectives)
assert 1.9 < c10 / c5 < 2.1, (c5, c10)
print("TRIPS-OK", r5.flops, r10.flops)
""", devices=8)
    assert "TRIPS-OK" in out


# ------------------------------------------------------ sharding rules


from repro.sharding.axes import BASELINE_RULES, FSDP_RULES, resolve_spec


class FakeMesh:
    def __init__(self, shape, names):
        import numpy as _np
        self.devices = _np.zeros(shape)
        self.axis_names = names


@hypothesis.given(
    dim=st.integers(min_value=1, max_value=4096),
    logical=st.sampled_from(["batch", "heads", "mlp", "vocab", "expert"]),
)
@hypothesis.settings(max_examples=60, deadline=None)
def test_resolve_spec_divisibility_property(dim, logical):
    """Resolved specs always evenly divide the dimension (never padded)."""
    mesh = FakeMesh((2, 16, 16), ("pod", "data", "model"))
    dropped = []
    spec = resolve_spec((logical,), (dim,), mesh, FSDP_RULES, dropped)
    entry = spec[0] if len(spec) > 0 else None
    if entry is not None:
        axes = entry if isinstance(entry, tuple) else (entry,)
        sizes = {"pod": 2, "data": 16, "model": 16}
        prod = 1
        for a in axes:
            prod *= sizes[a]
        assert dim % prod == 0


def test_resolve_spec_no_axis_reuse():
    mesh = FakeMesh((2, 16, 16), ("pod", "data", "model"))
    # batch takes (pod,data); a second batch-like dim must not reuse them
    spec = resolve_spec(("batch", "expert"), (32, 384), mesh, FSDP_RULES)
    used = []
    for entry in spec:
        if entry is None:
            continue
        used.extend(entry if isinstance(entry, tuple) else (entry,))
    assert len(used) == len(set(used))


def test_resolve_spec_fallback_replicates():
    mesh = FakeMesh((16, 16), ("data", "model"))
    dropped = []
    spec = resolve_spec(("kv_heads",), (2,), mesh, BASELINE_RULES, dropped)
    assert spec == ()  # replicated (trailing None trimmed)
    assert dropped == [("kv_heads", 2)]


# -------------------------------------------- multi-device integration


def test_mini_dryrun_multipod(subproc):
    """Scaled-down production mesh (2,2,2): lower+compile a smoke arch
    train step and a decode step; analyze collectives."""
    out = subproc("""
import jax
from repro.launch.mesh import make_mesh
from repro.launch import cells as C
import repro.configs.registry as R
import dataclasses

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
# shrink the cells: swap full config for smoke + small shape
orig = R.get_cell
def small_cell(arch, shape):
    cell = orig(arch, shape)
    smoke = R._module(arch).SMOKE
    sp = dataclasses.replace(cell.shape, global_batch=8, seq_len=32)
    st = dataclasses.replace(cell.settings, microbatches=2)
    return dataclasses.replace(cell, config=smoke, shape=sp, settings=st)
C.get_cell = small_cell
for arch, shape in [("qwen2_0_5b", "train_4k"), ("mixtral_8x22b", "train_4k"),
                    ("rwkv6_1_6b", "decode_32k"), ("whisper_small", "prefill_32k")]:
    built = C.build_cell(arch, shape, mesh)
    compiled = C.lower_cell(built).compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes >= 0
    from repro.core.hlo_analysis import analyze_compiled_text
    rep = analyze_compiled_text(compiled.as_text(), (2,2,2),
                                ("pod","data","model"))
    assert rep.flops > 0, arch
    print("MINI-OK", arch, shape, int(rep.flops), len(rep.collectives))
print("ALL-MINI-OK")
""", devices=8, timeout=420)
    assert "ALL-MINI-OK" in out


def test_compressed_pod_grads(subproc):
    out = subproc("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.train.compress import make_compressed_grad_fn, init_error_state
from repro.launch.mesh import make_mesh
mesh = make_mesh((2,2,2), ("pod","data","model"))
def loss_fn(params, batch):
    y = batch["x"] @ params["w"]
    l = jnp.mean((y - batch["t"])**2)
    return l, {"loss": l}
params = {"w": jnp.ones((8,8))*0.3}
batch = {"x": jnp.arange(64.).reshape(8,8)/10, "t": jnp.ones((8,8))}
fn = jax.jit(make_compressed_grad_fn(loss_fn, mesh, {"x": P("pod"), "t": P("pod")}))
err = init_error_state(params)
(l, m), g, err2 = fn(params, batch, err)
(_, _), g_ref = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
rel = float(jnp.max(jnp.abs(g["w"] - g_ref["w"])) / jnp.max(jnp.abs(g_ref["w"])))
assert rel < 0.02, rel
# error feedback: second call with the error state further reduces bias
(l2, _), g2, err3 = fn(params, batch, err2)
print("COMPRESS-OK", rel)
""", devices=8)
    assert "COMPRESS-OK" in out
