#!/usr/bin/env python3
"""The program against a cell's plain reference BY DEPTH, on seeded weights
at the configuration's own widths: where along the stack do the served
logits leave the float32 reference's?

    chiprun -- python3 scripts/depth_check.py [--config benchmarks/configs/<file>.json]
        [--tokens 2048] [--depths 1,2,3,6,12]

For each depth ``L`` the first ``L`` layers of the configuration are served
in its stated precision through ``llama.prefill_with_prefix`` (one sequence
through paged attention over a fresh cache: for an MLA model the absorbed
form over latent pages; it gives the logits of a row's last position, so
the one compiled program is run at ``POSITIONS`` lengths evenly spaced up
to ``--tokens``) and through the family's reference (float32, ``highest``),
and one line says how far the logits are apart at those positions: the
share whose first choice agrees, the gap ``check.py`` compares, and the RMS
error over the logits' spread. A model with an expert layer is read a second time
with its router's matmul at ``highest``. PR 40 used it to tell an attention
fault (none: 0.5 % at the dense layer) from near-tied experts that flip
under bfloat16 (3 % a layer of experts at the plain fan-in scale), through
the step function the program then had for all positions at once. It
refuses a model with recurrent state (it makes no state slots).
``--rehearse`` walks it at the rehearsal sizes on any backend.
"""
import argparse, functools, json, os, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp, numpy as np
from benchmarks import server, weights as W
from benchmarks.loading import load_data, load_family, load_module
from opsagent_tpu.models import llama

ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
ap.add_argument("--config", default="benchmarks/configs/glm47-flash-l12-int8.json")
ap.add_argument("--tokens", type=int, default=2048)
ap.add_argument("--depths", default="1,2,3,6,12")
ap.add_argument("--seed", type=int, default=4000000101)
ap.add_argument("--rehearse", action="store_true")
args = ap.parse_args()
T, SEED = args.tokens, args.seed
POSITIONS = 32
ENDS = np.unique(np.linspace(1, T, min(POSITIONS, T)).astype(np.int32))   # lengths; the position read is ENDS - 1
config = load_data(os.path.join(ROOT, args.config), rehearse=args.rehearse)
family = load_family(config); ref = load_module("reference", config["reference"])
if family.model_config(config).has_state:
    sys.exit("depth_check: a model with recurrent state is not served here")
tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (T,), 0, config["vocab_size"]))

def reference(cfg):
    sz, root = family.sizes(cfg), W.root_key(SEED)
    tables = family.position_tables(ref, T, cfg, sz)
    @functools.partial(jax.jit, static_argnames=("kind",))
    def layer_step(layer, x, kind):
        w = {n: W.as_float32(l) for n, l in family.layer_leaves(root, kind, layer, sz).items()}
        return family.apply_layer(ref, kind, x, w, tables, cfg, sz)
    x = W.embedding(root, family.LEAF_NO["embed"], sz["v"], sz["d"])[jnp.asarray(tokens)].astype(jnp.float32)
    outs = {}
    for _k, kind, first, count in family.stacks(sz):
        for l in range(first, first + count):
            x = layer_step(jnp.int32(l), x, kind)
            outs[l + 1] = x
    q, scale = W.matrix(root, family.LEAF_NO["lm_head"], 0, sz["d"], sz["v"])
    norm = W.norm(root, family.LEAF_NO["final_norm"], 0, sz["d"]).astype(jnp.float32)
    head = jax.jit(lambda x: ref.logits(x, norm, W.dequantize(q, scale), cfg["rms_norm_eps"]))
    return {L: np.asarray(head(x[ENDS - 1])) for L, x in outs.items()}

def program(cfg, precise_router=False):
    dtype = jnp.dtype(cfg["engine"]["dtype"])
    mc = family.model_config(cfg)
    params = server.program_tree(cfg, SEED)
    pages = T // 16 + 1
    cache = llama.make_cache(mc, pages, 16, dtype)
    table = jnp.arange(pages, dtype=jnp.int32)[None, :]
    orig = llama._route
    if precise_router:
        def route(h, lp, c):
            with jax.default_matmul_precision("highest"):
                return orig(h, lp, c)
        llama._route = route
    try:
        f = jax.jit(lambda p, t, n, c: llama.prefill_with_prefix(
            p, mc, t, jnp.zeros((1,), jnp.int32), n[None], c, table, dtype=dtype)[0])
        toks = jnp.asarray(tokens[None])
        return np.stack([np.asarray(f(params, toks, jnp.int32(n), cache)[0], np.float32) for n in ENDS])
    finally:
        llama._route = orig

def compare(name, got, want):
    best = want.max(-1); pick = got.argmax(-1)
    gap = best - np.take_along_axis(want, pick[:, None], -1)[:, 0]
    rel = np.sqrt(((got - want) ** 2).mean()) / want.std()
    print(json.dumps({"case": name, "agree": float((pick == want.argmax(-1)).mean()), "gap_max": float(gap.max()),
                      "gap_mean": float(gap.mean()), "rel_rms_err": float(rel), "logit_std": float(want.std())}), flush=True)

depths = [int(x) for x in args.depths.split(",")]
t0 = time.time()
want = reference(dict(config, num_hidden_layers=max(depths)))
print("reference", round(time.time() - t0, 1), "s", flush=True)
for L in depths:
    cfg = dict(config, num_hidden_layers=L)
    compare(f"L={L}", program(cfg), want[L])
    if family.model_config(cfg).moe is not None and L > cfg.get("first_k_dense_replace", 0):
        compare(f"L={L} router at highest", program(cfg, precise_router=True), want[L])
