"""Between a reference's weight layout and the program's parameter tree,
and the per-leaf norms both sides are compared by.

What every family's adapter shares is written here once: placing a tree as
the program's is placed (`place_like`, `place_for_reference`), the per-leaf
norms (`norm_functions`), the worst leaf's gap of norms (`worst_gap`), the
norm of the difference over sampled matrices (`diff_by_matrix`, `diff_gap`)
and a step's key as words (`key_data`). The rest is BERT's own
(families/bert.py binds it): the mapping from reference/bert_ref.py's layout
to the program's stacked-encoder tree, reshapes only (so it carries
gradients and parameter changes as well as weights, and keeps every norm),
and the encoder matrices compared whole. A program leaf the mapping does
not know is an error.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def to_program_tree(ref: dict, heads: int) -> dict:
    """The reference's tree in the program's stacked-encoder layout."""
    lay = ref["layers"]
    n, e, _ = lay["wqkv"].shape
    d = e // heads

    def ln(g, b):
        return {"scale": g, "bias": b}

    def dense(w, b):
        return {"kernel": w, "bias": b}

    layer = {
        "attention": {
            "qkv": dense(lay["wqkv"].reshape(n, e, 3, heads, d),
                         lay["bqkv"].reshape(n, 3, heads, d)),
            "output": dense(lay["wo"].reshape(n, heads, d, e), lay["bo"]),
        },
        "attention_layer_norm": ln(lay["ln1_g"], lay["ln1_b"]),
        "intermediate": dense(lay["w1"], lay["b1"]),
        "mlp_output": dense(lay["w2"], lay["b2"]),
        "output_layer_norm": ln(lay["ln2_g"], lay["ln2_b"]),
    }
    bert = {
        "embeddings": {
            "word_embeddings": {"embedding": ref["word"]},
            "position_embeddings": {"embedding": ref["pos"]},
            "token_type_embeddings": {"embedding": ref["type"]},
            "layer_norm": ln(ref["emb_ln_g"], ref["emb_ln_b"]),
        },
        "encoder": {"layers": {"layer": layer}},
    }
    tree = {"bert": bert}
    if "pool_w" in ref:
        bert["pooler"] = {"dense": dense(ref["pool_w"], ref["pool_b"])}
        tree["cls_predictions"] = {
            "transform": dense(ref["mlm_w"], ref["mlm_b"]),
            "layer_norm": ln(ref["mlm_ln_g"], ref["mlm_ln_b"]),
            "bias": ref["mlm_bias"],
        }
        tree["cls_seq_relationship"] = dense(ref["nsp_w"], ref["nsp_b"])
    return tree


def place_like(ours: dict, theirs):
    """`ours` (program layout) with every leaf cast and placed as the
    corresponding leaf of `theirs` is; the two trees must have the same
    leaves and shapes."""
    ours_flat = dict(jax.tree_util.tree_flatten_with_path(ours)[0])
    theirs_flat, treedef = jax.tree_util.tree_flatten_with_path(theirs)
    ours_keys = {jax.tree_util.keystr(k): v for k, v in ours_flat.items()}
    leaves = []
    for path, leaf in theirs_flat:
        key = jax.tree_util.keystr(path)
        if key not in ours_keys:
            raise KeyError(f"the program has a parameter the benchmark's "
                           f"adapter does not know: {key} {leaf.shape}")
        mine = ours_keys.pop(key)
        if mine.shape != leaf.shape:
            raise ValueError(f"{key}: benchmark weight {mine.shape} vs "
                             f"program parameter {leaf.shape}")
        leaves.append(jax.device_put(mine.astype(leaf.dtype), leaf.sharding))
    if ours_keys:
        raise KeyError(f"the program lacks parameters the reference has: "
                       f"{sorted(ours_keys)}")
    return jax.tree_util.tree_unflatten(treedef, leaves)


def place_for_reference(tree, rows_axis_sharded: bool):
    """On several chips the reference runs data-parallel over all of them:
    weights replicated, a micro-batch's rows split. One chip: as it is."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.local_devices()
    if len(devices) == 1:
        return jax.device_put(tree, devices[0])
    mesh = Mesh(np.array(devices), ("d",))
    spec = P("d") if rows_axis_sharded else P()
    return jax.device_put(tree, NamedSharding(mesh, spec))


def norm_functions(stacked):
    """(leaf_norms, leaf_diff_norms) of a family's trees. `stacked(path)`
    says whether the leaf at `path` is a stack along its first axis of what
    LAMB treats as one tensor each (BERT: a leaf stacked over layers; a
    routed layer's stack of experts): such a leaf has one norm per entry.
    leaf_norms(tree) -> {path: norms}; leaf_diff_norms(a, b): of a - b."""
    def norms(tree):
        def norm(path, x):
            x = x.astype(jnp.float32)
            axes = tuple(range(1 if stacked(path) else 0, x.ndim))
            return jnp.sqrt(jnp.sum(jnp.square(x), axis=axes)).reshape(-1)

        return jax.tree_util.tree_map_with_path(norm, tree)

    def by_path(tree) -> dict:
        return {jax.tree_util.keystr(k): np.asarray(v, np.float64)
                for k, v in jax.tree_util.tree_flatten_with_path(
                    jax.device_get(tree))[0]}

    of_tree = jax.jit(norms)
    of_diff = jax.jit(lambda a, b: norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))
    return (lambda tree: by_path(of_tree(tree)),
            lambda a, b: by_path(of_diff(a, b)))


# BERT: one norm per layer for a leaf stacked over layers
leaf_norms, leaf_diff_norms = norm_functions(lambda path: any(
    str(getattr(k, "key", k)) == "layers" for k in path))


SAMPLED = ("['attention']['qkv']['kernel']", "['attention']['output']['kernel']",
           "['intermediate']['kernel']", "['mlp_output']['kernel']")


def sample_matrices(tree) -> dict:
    """{path[layer]: float32 host array}: the encoder's four weight matrices
    of the first, the middle and the last layer, whole, from a tree in the
    program's layout (36 MB in BERT-Large's 12.6M-element layers, times 3)."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = jax.tree_util.keystr(path)
        if "['layers']" in key and key.endswith(SAMPLED):
            n = leaf.shape[0]
            for i in sorted({0, n // 2, n - 1}):
                out[f"{key}[{i}]"] = np.asarray(
                    jax.device_get(leaf[i]), np.float32)
    if not out:
        raise KeyError("no encoder matrix found to sample")
    return out


def diff_by_matrix(got: dict, want: dict) -> dict:
    """{name: |got - want| / |want|} of the sampled matrices: the norm of
    the DIFFERENCE, which rounding moves in first order (a gap of norms
    moves in second order only, PERF.md section 2)."""
    if sorted(got) != sorted(want):
        raise KeyError(f"sampled matrices differ: "
                       f"{sorted(set(got) ^ set(want))}")
    return {k: np.linalg.norm((got[k] - want[k]).ravel())
            / max(np.linalg.norm(want[k].ravel()), 1e-30) for k in want}


def diff_gap(got: dict, want: dict) -> float:
    """The MEAN of diff_by_matrix over the sampled matrices."""
    return float(np.mean(list(diff_by_matrix(got, want).values())))


def key_data(rng) -> np.ndarray:
    """A step's rng key as its raw uint32 words, on the host."""
    if jnp.issubdtype(rng.dtype, jax.dtypes.prng_key):
        rng = jax.random.key_data(rng)
    return np.asarray(jax.device_get(rng), np.uint32)


def worst_gap(got: dict, want: dict) -> dict:
    """The worst leaf's |got - want| over max(want, median of want): the gap
    between two NORMS, not the norm of a difference, measured against the
    reference's norm of that leaf or of the median leaf, whichever is larger
    (some leaves' gradients are all but zero)."""
    if sorted(got) != sorted(want):
        raise KeyError(f"leaf sets differ: {sorted(set(got) ^ set(want))}")
    median = float(np.median(np.concatenate([want[k] for k in want])))
    worst = {"gap": 0.0, "leaf": None}
    for key in want:
        gaps = np.abs(got[key] - want[key]) / np.maximum(want[key], median)
        i = int(np.argmax(gaps))
        if not np.isfinite(gaps).all():
            return {"gap": float("inf"), "leaf": key}
        if gaps[i] > worst["gap"]:
            worst = {"gap": float(gaps[i]), "leaf": f"{key}[{i}]",
                     "got": float(got[key][i]), "want": float(want[key][i])}
    worst["median_norm"] = median
    return worst
