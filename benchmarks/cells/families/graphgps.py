"""Family ``graphgps``: GraphGPS (Rampášek et al., arXiv:2205.12454) at
its LRGB Peptides-func configuration, on seeded peptide-like graphs.

The five functions of the family contract (``families/gnnbuilder.py``),
and ``attention_work``, the count that ``metrics/attn_roofline.screen``
reads. The model, as GraphGPS's code reads at inference (``i`` the
destination of an edge, ``j`` its source; BN is BatchNorm in eval mode,
the affine of its running statistics):

* node encoder ``h = [sum_f AtomEmb_f(x_f) ; LapPE]``, with
  ``LapPE_i = sum_k m_ik relu(W2 relu(W1 [phi_k(i), lambda_k] + b1) +
  b2)`` over the graph's ``pe_eigvecs`` Laplacian eigenpairs (``m_ik``
  0 where the graph has fewer); edge encoder ``e = sum_f BondEmb_f``;
* per layer, GatedGCN ``e'_ij = D h_i + E h_j + C e_ij``,
  ``h'_i = A h_i + sum_j s_ij B h_j / (sum_j s_ij + 1e-6)`` with
  ``s = sigmoid(e')``; ``h_loc = BN(h + relu(BN(h')))``,
  ``e <- e + relu(BN(e'))``; ``h_att = BN(h + MHA(h))``, multi-head
  attention over the nodes of the graph (packed in-projection with
  bias, scale ``dh ** -0.5``, out-projection with bias);
  ``h <- h_loc + h_att``; ``h <- BN(h + W4 relu(W3 h + b3) + b4)``;
* mean pooling and the ``san_graph`` head, widths halving to the
  targets with ReLU between.

Requests carry, per node, the atom's categorical ids, the eigenvector
entries, the eigenvalues and a 0/1 column per eigenpair that says it
exists (never NaN: admission rejects non-finite features); per edge the
bond's categorical ids.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import scipy.linalg
from threadpoolctl import threadpool_limits

from bench_reference import matmul

_HIGHEST = jax.lax.Precision.HIGHEST

# OGB atomic-number ids (atomic number - 1) of the elements peptides use
C, N, O, S = 5, 6, 7, 15
SINGLE, DOUBLE, AROMATIC = 0, 1, 3

# side chains: (element, parent, bond) per atom, residue-local indices
# (0 N, 1 CA, 2 C, 3 O, side chain from 4); ring closures (a, b, bond)
_A, _D, _S = AROMATIC, DOUBLE, SINGLE
RESIDUES = {
    "G": ([], []),
    "A": ([(C, 1, _S)], []),
    "S": ([(C, 1, _S), (O, 4, _S)], []),
    "C": ([(C, 1, _S), (S, 4, _S)], []),
    "V": ([(C, 1, _S), (C, 4, _S), (C, 4, _S)], []),
    "T": ([(C, 1, _S), (O, 4, _S), (C, 4, _S)], []),
    "P": ([(C, 1, _S), (C, 4, _S), (C, 5, _S)], [(6, 0, _S)]),
    "L": ([(C, 1, _S), (C, 4, _S), (C, 5, _S), (C, 5, _S)], []),
    "I": ([(C, 1, _S), (C, 4, _S), (C, 4, _S), (C, 5, _S)], []),
    "N": ([(C, 1, _S), (C, 4, _S), (O, 5, _D), (N, 5, _S)], []),
    "D": ([(C, 1, _S), (C, 4, _S), (O, 5, _D), (O, 5, _S)], []),
    "Q": ([(C, 1, _S), (C, 4, _S), (C, 5, _S), (O, 6, _D), (N, 6, _S)],
          []),
    "E": ([(C, 1, _S), (C, 4, _S), (C, 5, _S), (O, 6, _D), (O, 6, _S)],
          []),
    "M": ([(C, 1, _S), (C, 4, _S), (S, 5, _S), (C, 6, _S)], []),
    "K": ([(C, 1, _S), (C, 4, _S), (C, 5, _S), (C, 6, _S), (N, 7, _S)],
          []),
    "R": ([(C, 1, _S), (C, 4, _S), (C, 5, _S), (N, 6, _S), (C, 7, _S),
           (N, 8, _D), (N, 8, _S)], []),
    "H": ([(C, 1, _S), (C, 4, _S), (N, 5, _A), (C, 5, _A), (C, 6, _A),
           (N, 8, _A)], [(9, 7, _A)]),
    "F": ([(C, 1, _S), (C, 4, _S), (C, 5, _A), (C, 5, _A), (C, 6, _A),
           (C, 7, _A), (C, 8, _A)], [(10, 9, _A)]),
    "Y": ([(C, 1, _S), (C, 4, _S), (C, 5, _A), (C, 5, _A), (C, 6, _A),
           (C, 7, _A), (C, 8, _A), (O, 10, _S)], [(10, 9, _A)]),
    "W": ([(C, 1, _S), (C, 4, _S), (C, 5, _A), (C, 5, _A), (N, 6, _A),
           (C, 7, _A), (C, 7, _A), (C, 9, _A), (C, 10, _A), (C, 11, _A)],
          [(8, 9, _A), (12, 13, _A)]),
}
# natural abundance of the residues, in percent
ABUNDANCE = {"A": 8.3, "R": 5.5, "N": 4.1, "D": 5.5, "C": 1.4, "Q": 3.9,
             "E": 6.8, "G": 7.1, "H": 2.3, "I": 5.9, "L": 9.7, "K": 5.8,
             "M": 2.4, "F": 3.9, "P": 4.7, "S": 6.6, "T": 5.4, "W": 1.1,
             "Y": 2.9, "V": 6.9}
_VALENCE = {C: 4, N: 3, O: 2, S: 2}


# ------------------------------------------------------------ program --
def program_config(config: dict):
    """The program's ``GNNModelConfig`` with its ``gps`` blocks."""
    from repro.core.gnn_model import GNNModelConfig, MLPConfig
    from repro.core.gps import GPSConfig
    m = config["model"]
    d = m["hidden_dim"]
    gps = GPSConfig(num_heads=m["num_heads"], ffn_dim=m["ffn_dim"],
                    atom_vocab=tuple(m["atom_vocab"]),
                    bond_vocab=tuple(m["bond_vocab"]),
                    pe_eigvecs=m["pe_eigvecs"], pe_hidden=m["pe_hidden_dim"],
                    pe_dim=m["pe_dim"], bn_eps=m["bn_eps"])
    return GNNModelConfig(
        graph_input_feature_dim=gps.node_feat_dim(),
        graph_input_edge_dim=len(m["bond_vocab"]),
        gnn_hidden_dim=d, gnn_num_layers=m["num_layers"],
        gnn_output_dim=d, gnn_conv=m["local_conv"], gnn_activation="relu",
        global_pooling=(m["pooling"],),
        mlp_head=MLPConfig(in_dim=d, out_dim=m["num_targets"],
                           hidden_dims=tuple(m["head_dims"]),
                           activation="relu"),
        gnn_precision=config["precision"]["program"], gps=gps)


# ------------------------------------------------------------- weights --
def param_shapes(model: dict) -> dict:
    """Weight tree in the layout of the program's ``model_plan``."""
    d, f = model["hidden_dim"], model["ffn_dim"]
    atom_dim = d - model["pe_dim"]
    lin = lambda a, b: {"w": (a, b), "b": (b,)}   # noqa: E731
    bn = {"scale": (d,), "bias": (d,), "mean": (d,), "var": (d,)}
    tree = {"encoder": {
        "atom": {f"f{i}": (v, atom_dim)
                 for i, v in enumerate(model["atom_vocab"])},
        "pe": {"l1": lin(2, model["pe_hidden_dim"]),
               "l2": lin(model["pe_hidden_dim"], model["pe_dim"])},
        "bond": {f"f{i}": (v, d)
                 for i, v in enumerate(model["bond_vocab"])}}}
    tree["gps"] = {f"l{i}": {
        "local": {k: lin(d, d) for k in "ABCDE"},
        "bn_node": dict(bn), "bn_edge": dict(bn), "bn_local": dict(bn),
        "attn": {"in": lin(d, 3 * d), "out": lin(d, d)},
        "bn_attn": dict(bn), "ffn": {"l1": lin(d, f), "l2": lin(f, d)},
        "bn_ffn": dict(bn)} for i in range(model["num_layers"])}
    tree["mlp"] = {f"l{j}": lin(a, b)
                   for j, (a, b) in enumerate(head_widths(model))}
    return tree


def head_widths(model: dict) -> list:
    dims = [model["hidden_dim"]] + list(model["head_dims"]) \
        + [model["num_targets"]]
    return list(zip(dims[:-1], dims[1:]))


def init_params(model: dict, key):
    """Seeded float32 weights: matrices normal / sqrt(fan-in), biases
    normal * 0.1, embedding tables normal / sqrt(number of tables);
    BatchNorm scales 1 + normal * 0.1 and shifts normal * 0.1, and
    running statistics from ``calibrate_batch_norm`` (its input's own,
    the mean moved by normal * 0.1 deviations and the variance scaled by
    U(0.5, 1.5)): drawn outright, they would let the residual stream
    grow ~3.5x a layer, to attention logits in the thousands."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(model), is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(paths):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        name = path[-1].key
        parent = path[-2].key
        if name == "var":
            leaf = jax.random.uniform(k, shape, jnp.float32, 0.5, 1.5)
        elif name == "scale":
            leaf = 1.0 + 0.1 * z
        elif name in ("b", "bias", "mean"):
            leaf = 0.1 * z
        elif parent in ("atom", "bond"):
            n_tables = len(model["atom_vocab" if parent == "atom"
                                 else "bond_vocab"])
            leaf = z / np.sqrt(n_tables)
        else:
            leaf = z / np.sqrt(shape[0])
        leaves.append(leaf)
    return calibrate_batch_norm(
        jax.tree_util.tree_unflatten(treedef, leaves), model)


# --------------------------------------------------------------- graphs --
def _residue_count_target(rng, g: dict) -> int:
    """Heavy-tailed node count: log-normal with the configured mean,
    redrawn above ``max_nodes``."""
    sigma = g["size_sigma"]
    mu = np.log(g["target_nodes"]) - sigma * sigma / 2
    while True:
        n = int(rng.lognormal(mu, sigma))
        if g["min_nodes"] <= n <= g["max_nodes"]:
            return n


def make_peptide(g: dict, k: int, seed: int, idx: int) -> dict:
    """Graph ``idx`` of seed ``seed``: residues drawn by abundance are
    chained by peptide bonds until the next would pass a heavy-tailed
    node target; a share of the peptides close head to tail. Features
    follow the OGB vocabularies; LapPE is the ``k`` smallest eigenpairs
    of the unnormalised Laplacian (unit eigenvectors)."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, idx]))
    target = _residue_count_target(rng, g)
    names = list(ABUNDANCE)
    prob = np.array([ABUNDANCE[r] for r in names])
    prob /= prob.sum()
    elem, bonds, res_of = [], [], []
    aromatic: set = set()
    prev_c = -1
    while True:
        side, rings = RESIDUES[names[rng.choice(len(names), p=prob)]]
        size = 4 + len(side)
        if elem and len(elem) + size + 1 > target:
            break
        base = len(elem)
        elem += [N, C, C, O] + [a for a, _, _ in side]
        res_of += [base] * size
        local = [(0, 1, SINGLE), (1, 2, SINGLE), (2, 3, DOUBLE)] \
            + [(4 + i, p, b) for i, (_, p, b) in enumerate(side)] + rings
        for a, b, t in local:
            bonds.append((base + a, base + b, t))
            if t == AROMATIC:
                aromatic.update((base + a, base + b))
        if prev_c >= 0:
            bonds.append((prev_c, base, SINGLE))
        prev_c = base + 2
    if len(elem) > 12 and rng.random() < g["cyclic_share"]:
        bonds.append((prev_c, 0, SINGLE))           # head-to-tail
    else:
        elem.append(O)                              # C-terminal OXT
        bonds.append((prev_c, len(elem) - 1, SINGLE))
    n = len(elem)
    elem = np.asarray(elem)
    a, b, t = (np.asarray(c) for c in zip(*bonds))
    adj = np.zeros((n, n))
    adj[a, b] = adj[b, a] = 1.0
    deg = adj.sum(1).astype(np.int64)
    order = np.where(t == AROMATIC, 1.5, np.where(t == DOUBLE, 2.0, 1.0))
    bond_sum = np.zeros(n)
    np.add.at(bond_sum, a, order)
    np.add.at(bond_sum, b, order)
    valence = np.array([_VALENCE[int(x)] for x in elem])
    num_h = np.clip(np.floor(valence - bond_sum), 0, 4).astype(np.int64)
    in_ring = _ring_atoms(n, a, b)
    arom = np.zeros(n, bool)
    arom[list(aromatic)] = True
    sp2 = arom | np.isin(np.arange(n), np.concatenate(
        [a[t != SINGLE], b[t != SINGLE]]))
    ca = np.zeros(n, bool)
    ca[np.asarray(sorted(set(res_of))) + 1] = True
    chiral = np.where(ca & (deg > 2),
                      np.where(rng.random(n) < 0.95, 1, 2), 0)
    charge = np.full(n, 5)
    charged = (elem == N) & (num_h >= 2) & (deg == 1) \
        & (rng.random(n) < 0.3)
    charge[charged] = 6
    atoms = np.stack([elem, chiral, np.clip(deg + num_h, 0, 11), charge,
                      np.clip(num_h + charged, 0, 9), np.zeros(n, int),
                      np.where(sp2, 1, 2), arom, in_ring], axis=1)
    conj = (t != SINGLE) | (elem[a] == N) | (elem[b] == N)
    stereo = (t == DOUBLE) & (rng.random(len(t)) < 0.05)
    bond_feat = np.stack([t, stereo, conj], axis=1)
    lap = np.diag(adj.sum(1)) - adj
    m = min(k, n)
    vals, vecs = scipy.linalg.eigh(lap, subset_by_index=[0, m - 1])
    phi = np.zeros((n, k))
    lam = np.zeros((n, k))
    mask = np.zeros((n, k))
    phi[:, :m] = vecs[:, :m]
    lam[:, :m] = vals[None, :m]
    mask[:, :m] = 1.0
    node_feat = np.concatenate([atoms, phi, lam, mask], 1)
    edge_index = np.stack([np.concatenate([a, b]), np.concatenate([b, a])],
                          axis=1)
    edge_feat = np.concatenate([bond_feat, bond_feat])
    return {"node_feat": node_feat.astype(np.float32),
            "edge_index": edge_index.astype(np.int32),
            "edge_feat": edge_feat.astype(np.float32), "num_nodes": n,
            "num_edges": len(edge_index),
            "y": (rng.random(g["num_targets"]) < 0.3).astype(np.float32)}


def _ring_atoms(n: int, a, b) -> np.ndarray:
    """Atoms on a cycle: what stays after leaves are peeled off."""
    deg = np.zeros(n, np.int64)
    np.add.at(deg, a, 1)
    np.add.at(deg, b, 1)
    nbrs = [[] for _ in range(n)]
    for u, v in zip(a.tolist(), b.tolist()):
        nbrs[u].append(v)
        nbrs[v].append(u)
    alive = np.ones(n, bool)
    stack = [i for i in range(n) if deg[i] <= 1]
    while stack:
        u = stack.pop()
        if not alive[u]:
            continue
        alive[u] = False
        for v in nbrs[u]:
            if alive[v]:
                deg[v] -= 1
                if deg[v] == 1:
                    stack.append(v)
    return alive.astype(np.int64)


def make_pool(config: dict, seed: int, size: int) -> list:
    """``size`` distinct peptides of one seed (``make_peptide``); each
    request's arrays hold exactly its own nodes and edges. One LAPACK
    thread: the eigenproblems are small, and threads only cost here."""
    program_config(config)     # a program without GPS blocks fails here
    g, k = config["graphs"], config["model"]["pe_eigvecs"]
    with threadpool_limits(1):
        return [make_peptide(g, k, seed, i) for i in range(size)]


# ------------------------------------------------------------ reference --
def _bn(p, x, eps):
    return (x - p["mean"]) / jnp.sqrt(p["var"] + eps) * p["scale"] \
        + p["bias"]


def _embed(tables, ids):
    return sum(tables[f"f{f}"][ids[..., f]] for f in range(ids.shape[-1]))


def _run(params, blk, model: dict, mm, norm):
    """The model over dense blocks; ``norm(layer, name, x, mask)`` is each
    BatchNorm (its input ``x``, ``mask`` 1 on real nodes or edges)."""
    lin = lambda p, x: mm(x, p["w"]) + p["b"]               # noqa: E731
    relu = jax.nn.relu
    heads = model["num_heads"]
    x = blk["node_feat"]
    b, n, _ = x.shape
    e_max = blk["edge_src"].shape[1]
    node_ok = jnp.arange(n)[None] < blk["num_nodes"][:, None]
    edge_ok = jnp.arange(e_max)[None] < blk["num_edges"][:, None]
    maskf = node_ok[..., None].astype(jnp.float32)
    edgef = edge_ok[..., None].astype(jnp.float32)
    src = jnp.maximum(blk["edge_src"], 0)
    dst = jnp.maximum(blk["edge_dst"], 0)
    incoming = ((dst[..., None] == jnp.arange(n)) & edge_ok[..., None]) \
        .astype(jnp.float32).swapaxes(1, 2)                 # (B, N, E)
    take = lambda t, i: jnp.take_along_axis(t, i[..., None], axis=1)  # noqa
    sum_in = lambda t: jnp.matmul(incoming, t, precision=_HIGHEST)  # noqa

    na, k = len(model["atom_vocab"]), model["pe_eigvecs"]
    enc = params["encoder"]
    h_atom = _embed(enc["atom"], x[..., :na].astype(jnp.int32))
    pair = jnp.stack([x[..., na:na + k], x[..., na + k:na + 2 * k]], -1)
    z = relu(lin(enc["pe"]["l2"], relu(lin(enc["pe"]["l1"], pair))))
    pe = jnp.sum(z * x[..., na + 2 * k:na + 3 * k, None], axis=2)
    h = jnp.concatenate([h_atom, pe], -1) * maskf
    e = _embed(enc["bond"], blk["edge_feat"].astype(jnp.int32)) * edgef

    d = h.shape[-1]
    dh = d // heads
    for i in range(model["num_layers"]):
        p = params["gps"][f"l{i}"]
        loc = p["local"]
        e_hat = take(lin(loc["D"], h), dst) + take(lin(loc["E"], h), src) \
            + lin(loc["C"], e)
        gate = jax.nn.sigmoid(e_hat) * edgef
        agg = sum_in(gate * take(lin(loc["B"], h), src)) \
            / (sum_in(gate) + 1e-6)
        h_loc = h + relu(norm(i, "bn_node", lin(loc["A"], h) + agg, maskf))
        e = (e + relu(norm(i, "bn_edge", e_hat, edgef))) * edgef
        h_loc = norm(i, "bn_local", h_loc, maskf)
        qkv = lin(p["attn"]["in"], h).reshape(b, n, 3, heads, dh)
        q, kk, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk, precision=_HIGHEST) \
            * dh ** -0.5
        s = jnp.where(node_ok[:, None, None, :], s, -jnp.inf)
        att = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", att, v,
                       precision=_HIGHEST).reshape(b, n, d)
        h_att = norm(i, "bn_attn", h + lin(p["attn"]["out"], o), maskf)
        h = h_loc + h_att
        f = lin(p["ffn"]["l2"], relu(lin(p["ffn"]["l1"], h)))
        h = norm(i, "bn_ffn", h + f, maskf) * maskf
    out = jnp.sum(h, axis=1) / jnp.maximum(jnp.sum(maskf, axis=1), 1.0)
    widths = head_widths(model)
    for j in range(len(widths)):
        out = lin(params["mlp"][f"l{j}"], out)
        if j < len(widths) - 1:
            out = relu(out)
    return out


@functools.partial(jax.jit, static_argnames=("model_key", "precision"))
def _forward(params, blk, model_key, precision):
    model = dict(model_key)
    eps = model["bn_eps"]
    return _run(params, blk, model,
                functools.partial(matmul, precision=precision),
                lambda i, name, x, m: _bn(params["gps"][f"l{i}"][name], x,
                                          eps))


def calibrate_batch_norm(params, model: dict):
    """The BatchNorm running statistics a trained model would carry: each
    BatchNorm's input statistics over ``model["bn_calibration"]``'s
    peptides (drawn from the generator with its parameters), in order,
    every earlier BatchNorm already set; then shifted and scaled by the
    seeded draws ``init_params`` left in ``mean`` (N(0, 0.1^2), in units
    of the channel's deviation) and ``var`` (U(0.5, 1.5), a factor)."""
    cal = model["bn_calibration"]
    shape = dict(cal, num_targets=model["num_targets"])
    graphs = [make_peptide(shape, model["pe_eigvecs"], cal["seed"], i)
              for i in range(cal["graphs"])]
    blk = dense_block(
        graphs, -(-max(g["num_nodes"] for g in graphs) // 8) * 8,
        -(-max(g["num_edges"] for g in graphs) // 8) * 8)
    eps = model["bn_eps"]
    layers = {name: dict(layer) for name, layer in params["gps"].items()}

    def norm(i, name, x, m):
        axes = tuple(range(x.ndim - 1))
        count = jnp.sum(m)
        mean = jnp.sum(x * m, axis=axes) / count
        var = jnp.sum(jnp.square(x - mean) * m, axis=axes) / count
        p = layers[f"l{i}"][name]
        p = dict(p, mean=mean + p["mean"] * jnp.sqrt(var),
                 var=var * p["var"])
        layers[f"l{i}"][name] = p
        return _bn(p, x, eps)

    _run(params, blk, model, functools.partial(matmul, precision="highest"),
         norm)
    return dict(params, gps=layers)


def _freeze(model: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()
                        if not isinstance(v, dict)))


def dense_block(graphs: list, node_pad: int, edge_pad: int) -> dict:
    b = len(graphs)
    f = graphs[0]["node_feat"].shape[1]
    fe = graphs[0]["edge_feat"].shape[1]
    blk = {"node_feat": np.zeros((b, node_pad, f), np.float32),
           "edge_src": np.full((b, edge_pad), -1, np.int32),
           "edge_dst": np.full((b, edge_pad), -1, np.int32),
           "edge_feat": np.zeros((b, edge_pad, fe), np.float32),
           "num_nodes": np.zeros((b,), np.int32),
           "num_edges": np.zeros((b,), np.int32)}
    for i, m in enumerate(graphs):
        n, e = m["num_nodes"], m["num_edges"]
        if n > node_pad or e > edge_pad:
            raise ValueError(f"graph of {n} nodes/{e} edges exceeds the "
                             f"reference's pad ({node_pad}/{edge_pad})")
        blk["node_feat"][i, :n] = m["node_feat"][:n]
        blk["edge_src"][i, :e] = m["edge_index"][:e, 0]
        blk["edge_dst"][i, :e] = m["edge_index"][:e, 1]
        blk["edge_feat"][i, :e] = m["edge_feat"][:e]
        blk["num_nodes"][i] = n
        blk["num_edges"][i] = e
    return blk


def reference_outputs(params, config: dict, graphs: list, *,
                      precision: str = "highest", node_pad: int,
                      edge_pad: int, block_graphs: int) -> np.ndarray:
    """(len(graphs), num_targets) outputs of the plain reference, in
    float32 ``jax.numpy``, importing nothing of the program: each graph
    a dense block padded to ``node_pad`` nodes and ``edge_pad`` edges,
    sums over in-edges as products with the one-hot incidence matrix,
    attention dense over the graph's nodes; ``block_graphs`` graphs a
    call (the last block padded with copies). ``precision`` is how the
    weight matrices multiply (``bench_reference.matmul``); the sums
    over neighbours and the attention products stay at ``highest``."""
    key = _freeze(config["model"])
    out = []
    with jax.default_matmul_precision("highest"):
        for s in range(0, len(graphs), block_graphs):
            chunk = graphs[s:s + block_graphs]
            real = len(chunk)
            chunk = chunk + [chunk[-1]] * (block_graphs - real)
            blk = dense_block(chunk, node_pad, edge_pad)
            out.append(np.asarray(
                _forward(params, blk, key, precision))[:real])
    return np.concatenate(out, axis=0)


# ---------------------------------------------------------------- FLOPs --
def graph_flops(model: dict, num_nodes: int, num_edges: int) -> int:
    """Model FLOPs of one graph of ``n`` nodes and ``e`` directed edges,
    padding not counted. Conventions as ``bench_flops``: a linear map of
    ``r`` rows from ``a`` to ``b`` is ``2 r a b + r b``; one FLOP per
    element of an add, a product, a BatchNorm's subtract and multiply
    (two) or a reduction step; embedding lookups, activations, the
    sigmoid and the exponential cost nothing. Per layer at width ``d``:
    GatedGCN's four node maps and one edge map, the gate sum (2 e d),
    the gated message (e d), two sums over in-edges (2 e d), the
    division and the add (2 n d); attention's in- and out-projections,
    ``Q K^T`` and ``P V`` over the graph's ``n^2`` pairs (``4 n^2 d``)
    and the softmax's subtract, sum and divide (``3 H n^2``); the FFN;
    five BatchNorms (four on nodes, one on edges) and the residual adds
    (four on nodes, one on edges). The encoder: the atom and bond
    embedding sums, LapPE's two maps per eigenpair and the masked sum;
    then mean pooling and the head."""
    n, e = num_nodes, num_edges
    d, f, heads = model["hidden_dim"], model["ffn_dim"], model["num_heads"]
    k, ph, pd = model["pe_eigvecs"], model["pe_hidden_dim"], model["pe_dim"]

    def lin(r, a, b):
        return 2 * r * a * b + r * b

    total = (len(model["atom_vocab"]) - 1) * n * (d - pd) \
        + (len(model["bond_vocab"]) - 1) * e * d \
        + lin(n * k, 2, ph) + lin(n * k, ph, pd) + 2 * n * k * pd
    per_layer = 4 * lin(n, d, d) + lin(e, d, d) + 2 * e * d + e * d \
        + 2 * e * d + 2 * n * d \
        + lin(n, d, 3 * d) + 4 * n * n * d + 3 * heads * n * n \
        + lin(n, d, d) \
        + lin(n, d, f) + lin(n, f, d) \
        + 2 * (4 * n * d + e * d) + 4 * n * d + e * d
    total += model["num_layers"] * per_layer + n * d
    return int(total + sum(lin(1, a, b) for a, b in head_widths(model)))


def attention_work(model: dict, pairs: int, rows: int) -> tuple:
    """(FLOPs, bytes) of the attention kernels of one model over the
    given real query-key pairs (per head, summed over graphs) and real
    rows: ``Q K^T`` and ``P V``, ``4 d`` FLOPs a pair over all heads,
    and q, k, v and the output read or written once at fp32, in every
    layer. The same work whatever implements it."""
    d, layers = model["hidden_dim"], model["num_layers"]
    return (layers * 4 * d * pairs, layers * 4 * d * 4 * rows)
