"""Compile the main path's attention kernels for a TPU v5e that is
described, not attached: the chip's own compiler runs here and refuses
what it would refuse there (a slice off the tiling, too much VMEM), at
no chip time. Nothing executes -- a pass says the kernel compiles, not
that it is right or fast.

The code under test asks ``jax.default_backend()``, which is the CPU
here, so the tests steer it: the kernels' interpret switch is patched
off and the dispatcher is told its platform.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from analytics_zoo_tpu.ops import attention, pallas_attention


@pytest.fixture(scope="module")
def topo():
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _compiled_kernels_no_persistent_cache(monkeypatch):
    monkeypatch.setattr(pallas_attention, "_interpret", lambda: False)
    monkeypatch.setattr(attention, "_platform", lambda q: "tpu")
    # an executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without one: the next
    # run would warn on every entry and compile again
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _qkv(shape, sharding):
    return (jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                 sharding=sharding),) * 3


def _scalar(out):
    return jnp.sum(out.astype(jnp.float32))


def _shapes_on(tree, sharding):
    """The tree's leaves as shapes placed on the described chip."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("shape,causal", [
    ((48, 12, 384, 64), False),    # BERT-base b48 / L384
    ((4, 12, 2048, 64), True),     # long context, d64
    ((2, 16, 4096, 128), True),    # long context, d128
])
def test_owned_flash_compiles_forward_and_backward(one_chip, shape,
                                                   causal):
    def attn(q, k, v):
        return pallas_attention.pallas_flash_attention_fwd(q, k, v,
                                                           causal)

    args = _qkv(shape, one_chip)
    fwd = jax.jit(attn).lower(*args).compile().as_text()
    assert fwd.count("tpu_custom_call") == 1
    bwd = jax.jit(jax.grad(lambda q, k, v: _scalar(attn(q, k, v)),
                           argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    assert bwd.count("tpu_custom_call") == 2  # fwd+lse, one backward


@pytest.mark.parametrize("shape,heads", [
    ((32, 384, 768), 12),       # BERT-base b32 / L384: one grid step a row
    ((8, 512, 1024), 16),       # BERT-large at 512: two groups of 8 heads
])
def test_short_row_kernels_compile_forward_and_backward(one_chip, shape,
                                                        heads):
    """The short-row kernels through the dispatcher at the projection's
    own layout: one kernel forward, two with the backward, the VMEM they
    ask for granted, and no [L, L] tensor outside them."""
    args = _qkv(shape, one_chip)
    length = shape[1]

    def attn(q, k, v):
        return attention.packed_attention(q, k, v, heads)

    fwd = jax.jit(attn).lower(*args).compile().as_text()
    assert fwd.count("tpu_custom_call") == 1
    assert "attention_flash_short" in fwd
    bwd = jax.jit(jax.grad(lambda q, k, v: _scalar(attn(q, k, v)),
                           argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    assert bwd.count("tpu_custom_call") == 2
    assert f"{length},{length}" not in fwd + bwd


def test_short_row_kernels_partition_over_four_chips(topo):
    """One GSPMD program over a described 2x2 host, the batch over
    ``data`` and the weights replicated (the dp4 cell's layout): told
    the mesh, every chip runs the kernels on its own 32 rows and the
    program gathers nothing; the kernels keep the caller's scopes."""
    mesh = Mesh(topo.devices, ("data",))
    rows, whole = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    x = jax.ShapeDtypeStruct((128, 384, 768), jnp.bfloat16, sharding=rows)
    w = jax.ShapeDtypeStruct((768, 3, 768), jnp.bfloat16, sharding=whole)

    def loss(w, x):
        with jax.named_scope("layer"):
            qkv = jnp.einsum("blh,hpw->blpw", x, w)
            out = attention.packed_attention(
                qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], 12, mesh=mesh)
        return _scalar(out)

    text = jax.jit(jax.grad(loss)).lower(w, x).compile().as_text()
    assert "all-gather" not in text
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 2
    assert all("bf16[32,384," in line and "bf16[128," not in line
               for line in kernels)
    assert all("(layer))/attention_flash_short/" in line
               or "(layer)/attention_flash_short/" in line
               for line in kernels)


@pytest.mark.parametrize("length,path,kernels", [
    (16384, "fused", 1),     # the whole-sequence accumulators fit
    (32768, "split", 2),     # past the budget: dQ and dK/dV hold blocks
])
def test_backward_compiles_on_each_side_of_the_vmem_budget(
        one_chip, length, path, kernels):
    """The one-kernel backward asks for the VMEM its whole-sequence
    accumulators need, computed from its buffers; past
    ``FUSED_BWD_VMEM_BUDGET`` the two block-wise kernels take over. Both
    compile at d = 128, and the rule is a function of the shapes."""
    assert pallas_attention.flash_backward_path(
        length, length, 128, 128, 128, 2) == path
    q, k, v = _qkv((1, 2, length, 128), one_chip)
    lse = jax.ShapeDtypeStruct((2, length, 128), jnp.float32,
                               sharding=one_chip)

    def backward(q, k, v, out, lse, g):
        return pallas_attention._flash_bwd(q, k, v, out, lse, g, True,
                                           0.088, None, None)

    text = jax.jit(backward).lower(q, k, v, q, lse, q).compile().as_text()
    assert text.count("tpu_custom_call") == kernels
    assert f"{length},{length}" not in text     # no [L, L] tensor


def test_dispatcher_padding_mask_path_compiles(one_chip):
    """L1024 with a key-padding mask: the public dispatcher's
    segment-id path (the stock kernel), forward and backward."""
    b, h, l, d = 2, 12, 1024, 64
    args = _qkv((b, h, l, d), one_chip) + (
        jax.ShapeDtypeStruct((b, l), jnp.int32, sharding=one_chip),)

    def attn(q, k, v, kpm):
        return attention.dot_product_attention(q, k, v,
                                               key_padding_mask=kpm)

    fwd = jax.jit(attn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in fwd
    bwd = jax.jit(jax.grad(
        lambda q, k, v, kpm: _scalar(attn(q, k, v, kpm)),
        argnums=(0, 1, 2))).lower(*args).compile().as_text()
    assert "tpu_custom_call" in bwd


@pytest.mark.parametrize("window", [2048, None])
def test_window_and_grouped_heads_compile_at_8k(one_chip, window):
    """The sparse decoder's attention at its published widths: 32 query
    heads over 4 KV heads of 128, L8192, through the dispatcher. K/V
    reach the kernels with 4 heads (no repeat to 32 in HBM) and no
    [L, L] tensor exists forward or backward."""
    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.bfloat16,
                              sharding=one_chip)

    def attn(q, k, v):
        return attention.dot_product_attention(q, k, v, causal=True,
                                               window=window)

    bwd = jax.jit(jax.grad(lambda q, k, v: _scalar(attn(q, k, v)),
                           argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert bwd.count("tpu_custom_call") == 2  # fwd+lse, one backward
    assert "8192,8192" not in bwd
    assert "bf16[1,32,8192,128]" in bwd       # q, and never K/V:
    assert "bf16[32,8192,128]" in bwd
    assert f"(attention_flash{'_window' if window else ''})" in bwd


def test_latent_attention_kernels_compile_at_8k(one_chip):
    """Latent attention at its published widths through the dispatcher:
    16 heads, queries and keys 192 wide (128 of the head's own + the 64
    of one rotary key head that every head reads), values 128 wide,
    L8192. Forward + logsumexp and the one backward kernel compile; the
    rotary key reaches them as one head (no [1, 16, 8192, 192] keys in
    HBM), the values stay 128 wide, and no [L, L] tensor exists."""
    def on(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    args = (on((1, 16, 8192, 192)), on((1, 16, 8192, 128)),
            on((1, 1, 8192, 64)), on((1, 16, 8192, 128)))

    def attn(q, k_nope, k_rot, v):
        return attention.dot_product_attention(q, k_nope, v, causal=True,
                                               k_shared=k_rot)

    fwd = jax.jit(attn).lower(*args).compile().as_text()
    assert fwd.count("tpu_custom_call") == 1
    bwd = jax.jit(jax.grad(lambda *a: _scalar(attn(*a)),
                           argnums=(0, 1, 2, 3))).lower(
        *args).compile().as_text()
    assert bwd.count("tpu_custom_call") == 2  # fwd+lse, one backward
    assert "8192,8192" not in bwd
    assert "(attention_flash_latent)" in bwd
    kernels = [line for line in bwd.splitlines()
               if "tpu_custom_call" in line]
    # the joined keys exist in VMEM only: no kernel reads or writes a
    # 192-wide key or a 192-wide value
    assert all("bf16[16,8192,192]" in line for line in kernels)   # q / dq
    assert sum(line.count("bf16[16,8192,192]") for line in kernels) == 3
    assert all("bf16[1,8192,64]" in line for line in kernels)


def test_eva_attention_compiles_at_8k(one_chip):
    """EVA attention at the byte decoder's published widths through the
    dispatcher: 32 heads of 128, L8192 = four windows of 2,048, 512
    chunk summaries. Forward: the in-window causal kernel (the windows
    counted as heads) and one rectangular call over the summaries for
    each of windows 1-3; backward: as many again, each one kernel, given
    the joint output and logsumexp. No [L, L] and no [L, L / 16] score
    array exists, and every kernel answers to ``attention_flash_eva``."""
    def on(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    args = (on((1, 32, 8192, 128)),) * 3 + (on((1, 32, 512, 128)),) * 2

    def attn(*a):
        return attention.eva_attention(*a, window=2048)

    fwd = jax.jit(attn).lower(*args).compile().as_text()
    assert fwd.count("tpu_custom_call") == 4
    bwd = jax.jit(jax.grad(lambda *a: _scalar(attn(*a)),
                           argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile().as_text()
    kernels = [line for line in bwd.splitlines()
               if "tpu_custom_call" in line]
    assert len(kernels) == 8
    assert all("attention_flash_eva" in line for line in kernels)
    for scores in ("8192,8192", "8192,8704", "8192,512]", "2048,2048",
                   "2048,384]"):
        assert scores not in bwd, scores
    # the windows are heads of the in-window calls: a reshape, no copy
    assert sum("bf16[128,2048,128]" in line for line in kernels) == 2


def test_expert_layer_compiles_at_published_widths(one_chip, monkeypatch):
    """16 of 128 experts at d 2048 / width 1024 over 8,192 tokens,
    forward and backward: the grouped products are Pallas kernels under
    ``moe_experts`` (two forward and their transposes), the passes over
    the sorted buffer are loops under their scopes, and no buffer is
    wider than the worst case of 8 rows a token."""
    from analytics_zoo_tpu.keras.layers.moe import DroplessExperts

    module = DroplessExperts(width=1024, n_routed=128, n_held=16, top_k=8,
                             route_scale=2.826, shared_width=1024,
                             dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16,
                             sharding=one_chip)
    variables = jax.eval_shape(module.init, jax.random.PRNGKey(0),
                               jnp.zeros((1, 128, 2048), jnp.bfloat16))
    variables = _shapes_on(variables, one_chip)

    def loss(params, state, x):
        return _scalar(module.apply({"params": params, **state}, x))

    params = variables.pop("params")
    text = jax.jit(jax.grad(loss, argnums=(0, 2))).lower(
        params, variables, x).compile().as_text()
    assert "moe_experts/jit(gmm)/pallas_call" in text
    assert "moe_experts/jit(tgmm)/pallas_call" in text
    assert "bf16[65536,2048]" in text
    assert "[131072," not in text
    # (the gradient of a sum needs no forward combine)
    for loop in ("/jvp(DroplessExperts)/moe_dispatch/jit(_spread)",
                 "/jvp(DroplessExperts)/moe_experts/jit(_gate)",
                 "transpose(jvp(DroplessExperts))/moe_combine/jit(_spread)",
                 "transpose(jvp(DroplessExperts))/moe_experts/jit(_gate)",
                 "transpose(jvp(DroplessExperts))/moe_dispatch/jit(_collect)"):
        assert loop + "/while/body" in text, loop


def test_decoder_layers_backward_holds_two_kernels_a_layer(one_chip):
    """A window and a full layer of the sparse decoder at the published
    attention widths (32 query heads over 4 KV heads of 128, L8192),
    rematerialised as ``SparseDecoderModule`` declares it: the gradient
    compiles and holds forward + logsumexp and one backward kernel for
    each, and no third: the second forward finds the kernel's output
    and its logsumexp kept, and the backward regenerates the scores
    once for dQ, dK and dV."""
    from analytics_zoo_tpu.models.text.sparse_decoder_lm import (
        SparseDecoderModule, next_token_loss)

    module = SparseDecoderModule(
        vocab=1024, hidden_size=2048,
        layer_types=("sliding_attention", "full_attention"),
        n_dense_layers=2, n_head=32, n_kv_head=4, head_dim=128,
        window=2048, dense_width=6144, expert_width=1024, n_routed=128,
        n_held=16, dtype=jnp.bfloat16)
    kernels, text = _gradient_kernels(module, next_token_loss, one_chip)
    assert len(kernels) == 4
    for scope in ("attention_flash_window", "attention_flash"):
        assert sum(f"/{scope}/" in line for line in kernels) == 2, scope
    assert "8192,8192" not in text
    assert all("bf16[4,8192,128]" in line for line in kernels)   # K/V: 4


def test_latent_decoder_layers_backward_holds_two_kernels_a_layer(one_chip):
    """Two dense layers of the latent-attention decoder at the published
    attention widths (16 heads, 128 + 64 rotary | 128, latent 512,
    L8192) under the same rematerialisation: forward + logsumexp and one
    backward kernel a layer, the rotary key one head in HBM."""
    from analytics_zoo_tpu.models.text.sparse_decoder_lm import (
        LatentDecoderModule, next_token_loss)

    module = LatentDecoderModule(
        vocab=1024, hidden_size=2048, n_layers=2, n_dense_layers=2,
        n_head=16, nope_dim=128, rope_dim=64, v_dim=128, latent_dim=512,
        dense_width=11264, expert_width=1408, n_routed=64, n_held=8,
        dtype=jnp.bfloat16)
    kernels, text = _gradient_kernels(module, next_token_loss, one_chip)
    assert len(kernels) == 4
    assert all("/attention_flash_latent/" in line for line in kernels)
    assert "8192,8192" not in text
    assert all("bf16[1,8192,64]" in line for line in kernels)
    # 192 wide: q into the forward, q into and dq out of the backward;
    # never a key or a key's gradient
    assert sum(line.count("bf16[16,8192,192]") for line in kernels) == 2 * 3


def _one_layer_of(kind):
    """One dense layer of each decoder at its published widths (the
    vocabulary cut to 1,024 rows), its loss, and the products its second
    forward still holds."""
    from analytics_zoo_tpu.models.text import sparse_decoder_lm as lm

    sparse = dict(vocab=1024, hidden_size=2048, n_dense_layers=1,
                  dtype=jnp.bfloat16)
    if kind == "gated":        # Trinity-Mini; QK-norm's backward reads k
        return lm.SparseDecoderModule(
            **sparse, layer_types=("sliding_attention",), n_head=32,
            n_kv_head=4, head_dim=128, window=2048, dense_width=6144,
            expert_width=1024, n_routed=128, n_held=16), \
            lm.next_token_loss, {"attention/k"}
    if kind == "latent":       # Moonlight; the latent norm's reads kv_down
        return lm.LatentDecoderModule(
            **sparse, n_layers=1, n_head=16, nope_dim=128, rope_dim=64,
            v_dim=128, latent_dim=512, dense_width=11264, expert_width=1408,
            n_routed=64, n_held=8), lm.next_token_loss, {"attention/kv_down"}
    return lm.ByteDecoderModule(           # EvaByte
        vocab=320, hidden_size=4096, n_layers=1, n_head=32, head_dim=128,
        window=2048, chunk=16, dense_width=11008, n_pred_heads=8,
        dtype=jnp.bfloat16), lm.multi_byte_loss, set()


@pytest.mark.parametrize("kind", ["gated", "latent", "eva"])
def test_second_forward_holds_no_kept_product(one_chip, kind):
    """One rematerialised layer of each type at L8192, compiled with
    its gradient: the second forward holds none of the products whose
    results the layer keeps by name (SwiGLU's three, q, v, gate, out,
    ``kv_up``), only the narrow ones a norm's backward reads, and no
    attention kernel. Prints the temporaries' bytes, which is how much
    one layer's kept values and backward cost on the chip."""
    module, loss_of, left = _one_layer_of(kind)
    compiled = _gradient(module, loss_of, one_chip)
    text = compiled.as_text()
    assert set(re.findall(
        r'rematted_computation/layer_0/([\w/]+)/dot_general"', text)) == left
    assert not any("tpu_custom_call" in line and "rematted_computation" in line
                   for line in text.splitlines())
    memory = compiled.memory_analysis()
    print(f"{kind}: one layer's gradient at L8192 holds "
          f"{memory.temp_size_in_bytes / 1e6:.0f} MB of temporaries")
    assert memory.temp_size_in_bytes < 4e9


def _gradient(module, loss_of, sharding):
    """The compiled gradient of a decoder's loss at L8192."""
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=sharding)
    params = _shapes_on(jax.eval_shape(
        module.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 128), jnp.int32))["params"], sharding)

    def loss(params, ids):
        return loss_of(module.apply({"params": params}, ids), ids)

    return jax.jit(jax.grad(loss)).lower(params, ids).compile()


def _gradient_kernels(module, loss_of, sharding):
    """The Pallas kernels in the compiled gradient of a decoder's
    next-token loss at L8192, and the whole text."""
    text = _gradient(module, loss_of, sharding).as_text()
    return [line for line in text.splitlines()
            if "tpu_custom_call" in line], text


def test_looped_decoder_step_holds_one_body_and_no_whole_logits(one_chip):
    """One layer of the looped decoder applied four times at L8192 and
    the published widths, compiled with the exit-weighted loss's
    gradient: the passes are one loop (3 kernels for the layer --
    forward, the forward again, one backward -- not 4 x 3), the heads
    run in row blocks (no [8192, 49152] float32 array of any pass in
    the program), and the temporaries fit beside the state."""
    from analytics_zoo_tpu.models.text import looped_decoder_lm as looped

    module = looped.LoopedDecoderModule(
        vocab=49152, hidden_size=2048, n_layers=1, n_passes=4, n_head=16,
        head_dim=128, dense_width=5632, rope_theta=1e6, eps=1e-6,
        dtype=jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    params = _shapes_on(jax.eval_shape(
        module.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 128), jnp.int32))["params"], one_chip)

    def loss(params, ids):
        return looped.exit_weighted_loss(
            module.apply({"params": params}, ids, train=True), ids)

    compiled = jax.jit(jax.grad(loss)).lower(params, ids).compile()
    text = compiled.as_text()
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line]
    assert len(kernels) == 3
    assert sum("rematted_computation" in line for line in kernels) == 1
    assert not re.search(r"f32\[(\d+,)*8192,49152\]", text)
    assert re.search(r"f32\[1024,49152\]", text)       # a block of the heads
    memory = compiled.memory_analysis()
    print("looped: one layer x four passes at L8192 holds "
          f"{memory.temp_size_in_bytes / 1e6:.0f} MB of temporaries")
    assert memory.temp_size_in_bytes < 4e9


def test_hybrid_decoder_step_fits_with_its_optimizer_state(one_chip):
    """One period of the hybrid decoder (three Gated DeltaNet layers, one
    gated attention layer) at the published widths and the cell's cut
    (32 of 512 experts, 18,992 vocabulary rows), L8192, compiled with
    the next-token loss's gradient: the flash kernels run at heads of
    256 with 8 query heads a KV head in one forward and one backward
    kernel, the delta rule has no [L, L] tensor, its triangular solve
    is a kernel with the chunks on the lanes and its chunk recurrence a
    kernel pair with no loop of XLA's, and the gradient's temporaries
    fit beside the parameters and Adam's two moments under the
    compiler's 15.75 GB."""
    from analytics_zoo_tpu.models.text import hybrid_decoder_lm as hybrid
    from analytics_zoo_tpu.models.text.sparse_decoder_lm import (
        next_token_loss)

    module = hybrid.HybridDecoderModule(
        vocab=18992, hidden_size=2048, n_layers=4,
        full_attention_interval=4, n_head=16, n_kv_head=2, head_dim=256,
        partial_rotary_factor=0.25, n_k_head=16, n_v_head=32,
        head_k_dim=128, head_v_dim=128, expert_width=512, n_routed=512,
        n_held=32, top_k=10, shared_width=512, aux_loss_coef=0.001,
        rope_theta=1e7, eps=1e-6, dtype=jnp.bfloat16)
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one_chip)
    variables = _shapes_on(jax.eval_shape(
        module.init, jax.random.PRNGKey(0),
        jnp.zeros((1, 128), jnp.int32)), one_chip)
    params = variables.pop("params")
    variables.pop("losses", None)

    def loss(params, state, ids):
        logits, sown = module.apply(
            {"params": params, **state}, ids, train=True,
            mutable=["losses", "counters", "router_state"])
        return next_token_loss(logits, ids) + sum(
            jnp.sum(a) for a in jax.tree_util.tree_leaves(sown["losses"]))

    compiled = jax.jit(jax.grad(loss)).lower(params, variables,
                                             ids).compile()
    text = compiled.as_text()
    flash = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "/attention_flash/" in line]
    assert len(flash) == 2
    assert all("bf16[2,8192,256]" in line for line in flash)   # K/V: 2
    # the chunk recurrence is kernels, not a loop of XLA's
    assert not re.search(r"/linear_mixer/delta_rule/[^\"]*while", text)
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line
               and "/linear_mixer/delta_rule/" in line]
    # (I + A)^-1 once a GDN layer a forward: three layers, each in the
    # forward and in the backward's second forward (the layer's remat)
    solves = [line for line in kernels if "f32[64,64,4096]" in line]
    assert len(solves) == 6
    # the recurrence: the forward without the states, the second forward
    # with the float32 state entering each chunk, and the backward
    recurrences = [line for line in kernels
                   if "bf16[1,32,128,64,128]" in line]
    assert len(recurrences) == 9 and len(kernels) == 15
    states = [line for line in recurrences
              if "f32[1,32,128,128,128]" in line]
    assert len(states) == 6       # written by three, read by three
    # nothing left of the XLA substitution's lane-sparse [16, 16] blocks
    assert "f32[1,32,128,4,16,16]" not in text
    # no heads' [L, L] scores (the convolution's [1, L, 8,192 channels]
    # is no such tensor)
    assert not re.search(r"\[(1,)?(2|16|32),8192,8192\]", text)
    memory = compiled.memory_analysis()
    state = 3 * memory.argument_size_in_bytes     # + Adam's mu and nu
    print(f"hybrid: the gradient at L8192 holds "
          f"{memory.temp_size_in_bytes / 1e9:.2f} GB of temporaries beside "
          f"{state / 1e9:.2f} GB of parameters and moments")
    assert state + memory.temp_size_in_bytes < 15.75e9
