"""The builder of the cut family: the program's serving code round a step of
its own (a dense first layer, then routed experts of which the first
``num_experts`` are held here), with ``_params`` left as shapes: no weight is
allocated before the benchmark draws its own."""

from benchmark import later_cut_arithmetic as arithmetic


def _build(self):
    import jax
    import jax.numpy as jnp
    from jax import lax

    c = self.CONFIG
    D, H, V, M = c["hidden_size"], c["num_attention_heads"], c["vocab_size"], self.MAX_LEN
    Dh, K = D // H, c["num_experts_per_tok"]
    held, scored = c["num_experts"], arithmetic.routed(c)
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    layers = []
    for i in range(c["num_hidden_layers"]):
        layer = {"qkv": s(D, 3 * D), "proj": s(D, D)}
        if i < c["first_dense_layers"]:
            layer.update(mlp_in=s(D, c["intermediate_size"]),
                         mlp_out=s(c["intermediate_size"], D))
        else:
            F = c["moe_intermediate_size"]
            layer.update(router=s(D, scored), experts_in=s(held, D, F),
                         experts_out=s(held, F, D))
        layers.append(layer)
    self._params = {"embed": s(V, D), "pos": s(M, D), "layers": layers,
                    "unembed": s(D, V)}

    def norm(x):
        x32 = x.astype(jnp.float32)
        x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        return (x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-5)
                ).astype(x.dtype)

    def experts(h, layer):
        # softmax over every expert the router scores, the best K renormalised;
        # this chip adds the part its own experts give
        scores = jax.nn.softmax((h @ layer["router"]).astype(jnp.float32))
        best, which = lax.top_k(scores, K)
        gate = jnp.zeros(scored).at[which].set(best / jnp.sum(best))[:held]
        hidden = jax.nn.gelu(jnp.einsum("d,edf->ef", h, layer["experts_in"]))
        out = jnp.einsum("ef,efd->ed", hidden, layer["experts_out"])
        return (gate @ out.astype(jnp.float32)).astype(h.dtype)

    def step(params, caches, token, pos):
        x = params["embed"][token] + params["pos"][pos]
        new_caches = []
        for layer, cache in zip(params["layers"], caches):
            q, k_new, v_new = jnp.split(norm(x) @ layer["qkv"], 3)
            k = lax.dynamic_update_slice(cache["k"], k_new.reshape(H, 1, Dh), (0, pos, 0))
            v = lax.dynamic_update_slice(cache["v"], v_new.reshape(H, 1, Dh), (0, pos, 0))
            new_caches.append({"k": k, "v": v})
            scores = jnp.einsum("hd,hmd->hm", q.reshape(H, Dh).astype(jnp.float32),
                                k.astype(jnp.float32)) * Dh ** -0.5
            scores = jnp.where((jnp.arange(M) <= pos)[None, :], scores, -jnp.inf)
            attn = jnp.einsum("hm,hmd->hd", jax.nn.softmax(scores, axis=-1),
                              v.astype(jnp.float32))
            x = x + attn.reshape(D).astype(jnp.bfloat16) @ layer["proj"]
            h = norm(x)
            x = x + (experts(h, layer) if "router" in layer else
                     jax.nn.gelu(h @ layer["mlp_in"]) @ layer["mlp_out"])
        return (norm(x) @ params["unembed"]).astype(jnp.float32), new_caches

    self._step_fn = jax.jit(step, donate_argnums=1)


def generate(config, seed, **args):
    from client_tpu.models.decoder import TinyDecoderModel
    from client_tpu.models.generate import TinyGenerateModel

    cls = type("CutDecoder", (TinyDecoderModel,), {
        "CONFIG": config, "VOCAB": config["vocab_size"],
        "D_MODEL": config["hidden_size"], "HEADS": config["num_attention_heads"],
        "LAYERS": config["num_hidden_layers"],
        "MAX_LEN": config["max_position_embeddings"], "_build": _build})
    decoder = cls(seed=seed, **args)
    return TinyGenerateModel(decoder=decoder), decoder
