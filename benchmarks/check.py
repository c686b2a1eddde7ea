"""The comparison that decides ``correct``.

After the window has closed, a sample of the requests it finished (the
longest among them) is run once through the plain reference: the prompt the
client sent, as token ids, followed by the tokens the client was served.
At every served position the reference gives its own logits; the number
compared is the gap by which the served token's reference logit lies below
the reference's best allowed token. A greedy program that computes the
stated model in the stated precision serves, at every position, a token
within a small gap; lower precision, a wrong template, a dropped bias or an
altered token does not.

A reply constrained to a JSON schema is checked where the grammar leaves the
choice free: inside string values, where any byte from 0x20 to 0xff may
come next (a character, the closing quote or a backslash). That rule is
stated here from the JSON grammar, not read from the program's tables.

``control_bits=4`` also runs the control: the reference itself with every
matrix rounded to int4, put in the program's place. At each of the same
positions the token it puts first is read against the float32 reference.
The program's own lower-precision path (``run.py --engine
kv_quantize=int8``) reads inside the sound runs' noise here, so it is held
by ``precision_mismatches``: the program's own report against the
configuration's stated precision.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from benchmarks.loading import load_family, load_module

SEQ_BUCKET = 1024      # sequences pad to a multiple: few shapes compile
ROW_BUCKET = 256       # so do a block's compared positions
BLOCK_REQUESTS = 6     # sequences that go through the reference side by side
STRING_BYTES = (0x20, 0x100)


def free_positions(reply: list[int]) -> tuple[list[int], list[int]]:
    """(indices of ``reply`` that were a free choice inside a JSON string
    value, indices that no JSON text can hold)."""
    free, illegal = [], []
    in_string = is_value = False
    escape = 0          # characters of an escape still to come
    after_backslash = False
    last = ""
    for i, t in enumerate(reply):
        if t >= 256:
            illegal.append(i)
            continue
        c = chr(t)
        if not in_string:
            if c == '"':
                in_string, is_value = True, last == ":"
            elif c not in " \t\n\r":
                last = c
            continue
        if after_backslash:
            after_backslash = False
            escape = 4 if c == "u" else 0
            continue
        if escape:
            escape -= 1
            continue
        if is_value:
            free.append(i)
        if c == "\\":
            after_backslash = True
        elif c == '"':
            in_string, last = False, '"'
    return free, illegal


SAMPLE_GROWTH = 4      # times ``max_requests``, where replies leave little free


def checkable(sample: dict) -> int:
    """The served tokens of a request that the comparison can judge: every
    token of a free reply, the free positions of a constrained one."""
    reply = sample["reply_ids"]
    if sample.get("constrained"):
        return len(free_positions(list(reply))[0])
    return len(reply)


def select(finished: list[dict], rng, min_tokens: int,
           max_requests: int, min_checkable: int = 0) -> list[dict]:
    """The longest finished request, then others drawn by ``rng`` until the
    sample holds ``min_tokens`` served tokens or ``max_requests``. Where the
    requests name their ``client``, one of each client comes before a second
    of any: a fault that sits in one client's row is then in the sample.

    Seeded weights now and then make a model that closes every string at
    once, and its constrained replies leave three or four free positions
    each: the sample then grows past ``max_requests``, in the same order,
    until it holds ``min_checkable`` tokens the comparison can judge (the
    cell's ``min_checked_tokens``), or ``SAMPLE_GROWTH`` times as many
    requests. Every other sample is what it was."""
    if not finished:
        return []
    order = sorted(
        range(len(finished)),
        key=lambda i: -(len(finished[i]["prompt_ids"])
                        + len(finished[i]["reply_ids"])),
    )
    rest = order[1:]
    rng.shuffle(rest)
    seen = {finished[order[0]].get("client")}
    first, again = [], []
    for i in rest:
        client = finished[i].get("client")
        if client is None or client not in seen:
            seen.add(client)
            first.append(i)
        else:
            again.append(i)
    picked, tokens, judged = [], 0, 0
    for i in [order[0], *first, *again]:
        picked.append(finished[i])
        tokens += len(finished[i]["reply_ids"])
        judged += checkable(finished[i]) if min_checkable else 0
        full = tokens >= min_tokens or len(picked) >= max_requests
        if full and (judged >= min_checkable
                     or len(picked) >= SAMPLE_GROWTH * max_requests):
            break
    return picked


def run_check(config: dict, seed: int, samples: list[dict],
              control_bits: int = 0, block: int = BLOCK_REQUESTS) -> dict:
    """Gaps of the served tokens of ``samples`` against the reference.

    Each sample: ``prompt_ids``, ``reply_ids`` and ``constrained``. Returns
    the numbers compared, with what they were computed over."""
    import jax
    import jax.numpy as jnp

    from benchmarks import weights as W

    t0 = time.perf_counter()
    family = load_family(config)
    ref = load_module("reference", config["reference"])
    sz, no = family.sizes(config), family.LEAF_NO
    eps = config["rms_norm_eps"]
    root = W.root_key(seed)

    seqs, rows, served, allowed, illegal = [], [], [], [], 0
    for s in samples:
        prompt, reply = list(s["prompt_ids"]), list(s["reply_ids"])
        if s.get("constrained"):
            idx, bad = free_positions(reply)
            illegal += len(bad)
            lo, hi = STRING_BYTES
        else:
            idx, (lo, hi) = list(range(len(reply))), (0, sz["v"])
        if not idx:
            continue
        seqs.append(np.asarray(prompt + reply[:-1], np.int32))
        rows.append(np.asarray(idx, np.int32) + len(prompt) - 1)
        served.append(np.asarray([reply[i] for i in idx], np.int32))
        allowed.append((lo, hi))
    if not seqs:
        return {"checked_tokens": 0, "illegal_tokens": illegal,
                "requests": 0, "seconds": 0.0}

    # The sample goes through in blocks of ``block`` sequences, all padded
    # to the bucket of the longest, and a block's compared positions pad to
    # a multiple of ROW_BUCKET: a cell compiles a few shapes, and only one
    # block's float32 activations are live at a time.
    length = -(-max(len(ids) for ids in seqs) // SEQ_BUCKET) * SEQ_BUCKET
    tables = family.position_tables(ref, length, config, sz)

    # One jitted call makes a layer's weights from the seed and applies the
    # layer to a block's sequences, so a layer's float32 matrices live only
    # inside the call; the head is applied in blocks of the vocabulary for
    # the same reason. The family says which leaves a layer of each kind
    # has and how the reference is called on them.
    @functools.partial(jax.jit, static_argnames=("kind", "bits"),
                       donate_argnums=2)
    def layer_step(root, layer, x, kind: str, bits: int):
        w = {
            name: W.as_float32(leaf, bits)
            for name, leaf in family.layer_leaves(root, kind, layer, sz).items()
        }
        return jax.vmap(lambda seq: family.apply_layer(
            ref, kind, seq, w, tables, config, sz))(x)

    vocab_blocks = 8 if sz["v"] % 8 == 0 else 1

    @functools.partial(jax.jit, static_argnames=("bits",))
    def head_logits(root, x, bits: int):
        q, scale = W.matrix(root, no["lm_head"], 0, sz["d"], sz["v"])
        final_norm = W.norm(root, no["final_norm"], 0, sz["d"])
        final_norm = final_norm.astype(jnp.float32)
        qs = q.reshape(sz["d"], vocab_blocks, -1).transpose(1, 0, 2)

        def one(part):
            qb, sb = part
            head = W.dequantize(qb, sb, weight_bits=bits)
            return ref.logits(x, final_norm, head, eps)

        out = jax.lax.map(one, (qs, scale.reshape(vocab_blocks, -1)))
        return out.transpose(1, 0, 2).reshape(x.shape[0], sz["v"])

    @jax.jit
    def embed_rows(root, tok):
        table = W.embedding(root, no["embed"], sz["v"], sz["d"])
        return table[tok].astype(jnp.float32)

    def all_logits(tok, flat_rows, bits: int):
        x = embed_rows(root, jnp.asarray(tok))
        for _key, kind, first, count in family.stacks(sz):
            for l in range(first, first + count):
                x = layer_step(root, jnp.int32(l), x, kind=kind, bits=bits)
        return head_logits(root, x.reshape(-1, sz["d"])[flat_rows], bits=bits)

    @jax.jit
    def compare(truth, token, lo, hi):
        """Of each row: the best allowed logit, the served token's, whether
        it was allowed, and the allowed token the reference puts first."""
        col = jnp.arange(sz["v"])[None, :]
        masked = jnp.where((col >= lo[:, None]) & (col < hi[:, None]),
                           truth, -jnp.inf)
        own = jnp.take_along_axis(truth, token[:, None], axis=-1)[:, 0]
        legal = (token >= lo) & (token < hi)
        return jnp.max(masked, -1), own, legal, jnp.argmax(masked, -1)

    gaps, agree, gaps_c, agree_c, not_legal = [], [], [], [], 0
    for start in range(0, len(seqs), block):
        part = range(start, min(start + block, len(seqs)))
        tok = np.zeros((block, length), np.int32)
        for j, i in enumerate(part):
            tok[j, :len(seqs[i])] = seqs[i]
        flat = np.concatenate([rows[i] + j * length for j, i in enumerate(part)])
        n = flat.size
        padded = -(-n // ROW_BUCKET) * ROW_BUCKET

        def pad(values, fill=0):
            out = np.full(padded, fill, np.int32)
            out[:n] = values
            return jnp.asarray(out)

        token = pad(np.concatenate([served[i] for i in part]))
        lo = pad(np.concatenate(
            [np.full(len(rows[i]), allowed[i][0]) for i in part]))
        hi = pad(np.concatenate(
            [np.full(len(rows[i]), allowed[i][1]) for i in part]), sz["v"])
        flat_rows = pad(flat)
        truth = all_logits(tok, flat_rows, 8)
        best, own, legal, first = (
            np.asarray(a)[:n] for a in compare(truth, token, lo, hi))
        not_legal += int((~legal).sum())
        gaps.append(np.where(legal, best.astype(np.float64) - own, np.inf))
        agree.append(first == np.asarray(token)[:n])
        if control_bits:
            low = all_logits(tok, flat_rows, control_bits)
            tok_c = compare(low, token, lo, hi)[3]
            own_c = np.asarray(compare(truth, tok_c, lo, hi)[1])[:n]
            gaps_c.append(best.astype(np.float64) - own_c)
            agree_c.append(np.asarray(tok_c)[:n] == first)
    gap = np.concatenate(gaps)
    out = {
        "requests": len(seqs),
        "checked_tokens": int(gap.size),
        "illegal_tokens": illegal + not_legal,
        "longest_sequence": int(max(len(s) for s in seqs)),
        "gap_max": float(gap.max()),
        "gap_mean": float(gap.mean()),
        "agree_share": float(np.concatenate(agree).mean()),
    }
    if control_bits:
        gap_c = np.concatenate(gaps_c)
        out["control"] = {
            "weight_bits": control_bits,
            "gap_max": float(gap_c.max()),
            "gap_mean": float(gap_c.mean()),
            "agree_share": float(np.concatenate(agree_c).mean()),
        }
    out["seconds"] = time.perf_counter() - t0
    return out


def precision_mismatches(stated: dict, impl: dict) -> list[str]:
    """Where what the program says it serves (its ``impl_info``) is not
    the precision the configuration states. The comparison of outputs
    holds the program to the stated model within the noise of the stated
    precision; a storage type that sits inside that noise (int8 pages do,
    PERF.md section 2) is held by this declaration instead."""
    pages = impl.get("kv_quantize") or "none"
    served = {
        "weights": impl.get("quantize") or impl.get("dtype"),
        "compute": impl.get("dtype"),
        "kv_pages": impl.get("dtype") if pages == "none" else pages,
    }
    return [f"{key}: stated {stated.get(key)}, the program reports {got}"
            for key, got in served.items() if stated.get(key) != got]


def comparisons(numbers: dict, limits: dict) -> list[tuple]:
    """(name, number, ``<=`` or ``>=``, limit, met) for each number compared."""
    checks = [
        ("checked_tokens", numbers.get("checked_tokens", 0),
         ">=", limits["min_checked_tokens"]),
        ("illegal_tokens", numbers.get("illegal_tokens", 0), "<=", 0),
        ("precision_mismatches", numbers.get("precision_mismatches", 0),
         "<=", 0),
    ]
    for name in ("gap_max", "gap_mean"):
        if name in limits:
            checks.append(
                (name, numbers.get(name, float("inf")), "<=", limits[name]))
    return [
        (name, value, op, limit,
         bool(value >= limit if op == ">=" else value <= limit))
        for name, value, op, limit in checks
    ]


def verdict(numbers: dict, limits: dict) -> tuple[bool, list[str]]:
    """(correct, one line for each number beside its limit)."""
    rows = comparisons(numbers, limits)
    lines = [
        f"compared {name}: {value!r} (limit {op} {limit!r}) "
        f"{'ok' if good else 'NOT MET'}"
        for name, value, op, limit, good in rows
    ]
    return all(row[-1] for row in rows), lines
