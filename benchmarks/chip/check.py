"""What decides ``correct``: the program's readings, the reference's, and
the comparison of the two against the cell's limits.

Readings of a training run over its first steps (set-up drives them through
the window's own ``Trainer.run`` and feed):

* ``losses``: the loss of each step;
* ``grad_norms``: per leaf, the norm of the gradient as the optimizer got it
  at step 1, from its first moment after that step (``m = (1 - b1) g``);
* ``change_norms``: per leaf, the norm of the parameters' change after the
  last of those steps. A row's start is its initial value by the
  reference's rule, so a row that the program initialised wrongly moves;
* ``rows``: every live row after the last step, with its key, per group;
* ``dense``: the towers' parameters after the last step;
* ``m1``: the first moments after step 1, of the towers per leaf and of
  every row that step touched, with its key: ``(1 - b1)`` times the
  gradient each optimizer got, element by element.

Leaves are each tower parameter (``dense/<path>``) and each dim-group's
table (``sparse/dim<d>``). The numbers compared:

* ``loss_gap``: the largest |program − reference| loss over the steps;
* ``grad_gap`` / ``change_gap``: over the leaves, the largest gap between
  the program's norm and the reference's, as a share of the larger of the
  reference's norm of that leaf and of the median leaf. ``change_gap``
  leaves out a leaf whose reference gradient is under a thousandth of the
  median leaf's: Adam moves such a leaf by round-off alone;
* ``row_gap``: per group, the norm of the difference between the program's
  rows and the reference's after the last step, over the reference's
  change of those rows (a key the program lacks counts as unmoved, a row
  the reference lacks counts with its whole change); the worst group;
* ``dense_gap``: the same over all the towers' parameters;
* ``moment_gap``: per group, the norm of the difference between the
  program's and the reference's first moments after step 1, matched by
  key, over the reference's norm (a row on one side only counts whole),
  and the same over all the towers' moments; the worst of those. Adam's
  first step moves an element by about its learning rate whatever its
  gradient's size, so rows and towers after a step see mostly which signs
  a rounding flipped; the moments see the gradient itself;
* ``sign_flip_share``: per group and over the towers, the share of the
  reference's nonzero first-moment elements after step 1 whose sign the
  program's differs from (the direction of the element's first Adam
  update); the largest.

A cell's limits file names the numbers it compares; the others are
reported beside them with no limit.
"""
from __future__ import annotations

import statistics

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap", "row_gap", "dense_gap", "moment_gap",
           "sign_flip_share")
TINY = 1e-3


def flat(tree, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flat(v, name + "/"))
        else:
            out[name] = v
    return out


# ------------------------------------------------------------ program side
def grad_norms(state, b1: float) -> dict:
    """Step-1 gradient norms from the optimizers' first moments."""
    import jax
    import jax.numpy as jnp

    def norms(st):
        out = {f"dense/{k}": jnp.linalg.norm(v) / (1 - b1)
               for k, v in flat(st["opt"]["m"]).items()}
        for g, sub in st["sparse"].items():
            out[f"sparse/{g}"] = jnp.linalg.norm(sub["blocks"].slots["m"]) / (1 - b1)
        return out

    return {k: float(v) for k, v in jax.jit(norms)(state).items()}


def change_norms(state, dense0: dict, init_rows, mesh, chunk: int = 1 << 16) -> dict:
    """Norms of each leaf's change since the start. ``dense0``: host copy of
    the towers' parameters before step 1; ``init_rows(keys, dim)``: the
    reference's initial rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    out = {f"dense/{k}": float(np.linalg.norm(np.asarray(v, np.float64) - dense0[k]))
           for k, v in flat(jax.device_get(state["dense"])).items()}
    axes = tuple(mesh.axis_names)

    def shard_sq(keys, occ, offs, emb):
        keys, occ, offs, emb = keys[0], occ[0], offs[0], emb[0]
        cap, dim = keys.shape[0], emb.shape[1]
        n = -(-cap // chunk)
        pad = n * chunk - cap
        keys = jnp.pad(keys, (0, pad))
        live = jnp.pad(occ & (offs != 0), (0, pad))
        offs = jnp.pad(offs, (0, pad))

        def body(i, acc):
            k = jax.lax.dynamic_slice(keys, (i * chunk,), (chunk,))
            o = jax.lax.dynamic_slice(offs, (i * chunk,), (chunk,))
            lv = jax.lax.dynamic_slice(live, (i * chunk,), (chunk,))
            d = (emb[o] - init_rows(k, dim)) * lv[:, None]
            return acc + jnp.sum(d * d)

        return jax.lax.fori_loop(0, n, body, jnp.zeros((), jnp.float32))[None]

    sq = jax.jit(jax.shard_map(shard_sq, mesh=mesh, in_specs=(P(axes),) * 4,
                               out_specs=P(axes), check_vma=False))
    for g, sub in state["sparse"].items():
        m, b = sub["idmap"], sub["blocks"]
        out[f"sparse/{g}"] = float(np.sqrt(np.sum(np.asarray(
            sq(m.keys, m.occupied, m.offsets, b.emb), np.float64))))
    return out


def live_rows(state, mesh, slot: str = "emb") -> dict:
    """{group: {"keys", slot}} of every live row, on the host: ``slot`` is
    ``"emb"``, the rows, or one of the optimizer's slots (``"m"``)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)
    out = {}
    for g, sub in state["sparse"].items():
        m, b = sub["idmap"], sub["blocks"]
        table = b.emb if slot == "emb" else b.slots[slot]
        live = m.occupied & (m.offsets != 0)
        n = int(jnp.max(jnp.sum(live, axis=1)))
        size = max(1024, 1 << max(n - 1, 0).bit_length())

        def shard(keys, live, offs, emb):
            idx = jnp.nonzero(live[0], size=size, fill_value=0)[0]
            return keys[0][idx][None], emb[0][offs[0][idx]][None], live[0].sum()[None]

        k, e, c = jax.device_get(jax.jit(jax.shard_map(
            shard, mesh=mesh, in_specs=(P(axes),) * 4, out_specs=P(axes),
            check_vma=False))(m.keys, live, m.offsets, table))
        out[g] = {"keys": np.concatenate([k[i, :c[i]] for i in range(len(c))]),
                  slot: np.concatenate([e[i, :c[i]] for i in range(len(c))])}
    return out


def moments(state, mesh) -> dict:
    """``m1`` after step 1: the towers' first moments per leaf and each
    group's live rows' (after step 1, the rows it touched), on the host."""
    import jax

    return {"dense": {k: np.asarray(v)
                      for k, v in flat(jax.device_get(state["opt"]["m"])).items()},
            "rows": live_rows(state, mesh, "m")}


# -------------------------------------------------------------- comparison
def _match(prog: dict, slot: str, ref_keys, ref_vals, start):
    """The program's ``slot`` values at the reference's keys (``start`` of
    a key it lacks), the reference's starts, and the program's values at
    no key of the reference less their start."""
    dim = ref_vals.shape[1]
    keys = prog.get("keys", np.zeros(0, np.int64))
    vals = prog.get(slot, np.zeros((0, dim), ref_vals.dtype))
    order = np.argsort(keys)
    keys, vals = keys[order], vals[order]
    init = start(ref_keys, dim)
    if not len(keys):
        return init, init, np.zeros((0, dim))
    pos = np.clip(np.searchsorted(keys, ref_keys), 0, len(keys) - 1)
    hit = keys[pos] == ref_keys
    extra = np.ones(len(keys), bool)
    extra[pos[hit]] = False
    rest = vals[extra].astype(np.float64) - start(keys[extra], dim) if extra.any() \
        else np.zeros((0, dim))
    return np.where(hit[:, None], vals[pos], init), init, rest


def _ratio(diff: float, moved: float) -> float:
    gap = float(np.sqrt(diff / moved)) if moved > 0 else float("inf")
    return gap if np.isfinite(gap) else float("inf")


def _row_gap(rows: dict, ref_tables: dict, init_rows) -> tuple[float, str]:
    worst, group = 0.0, ""
    for g, t in sorted(ref_tables.items()):
        prog, init, rest = _match(rows.get(g, {}), "emb", t["keys"], t["emb"], init_rows)
        diff = np.sum((prog.astype(np.float64) - t["emb"]) ** 2) + np.sum(rest ** 2)
        gap = _ratio(diff, np.sum((t["emb"].astype(np.float64) - init) ** 2))
        if gap >= worst:
            worst, group = gap, f"sparse/{g}"
    return worst, group


def _moment_gap(prog: dict, ref: dict) -> tuple[tuple[float, str], tuple[float, str]]:
    """(``moment_gap``, ``sign_flip_share``), each with its worst part."""
    def start(keys, dim):
        return np.zeros((len(keys), dim))

    parts = {}
    for g, t in sorted(ref["rows"].items()):
        p, _, rest = _match(prog["rows"].get(g, {}), "m", t["keys"], t["m"], start)
        parts[f"sparse/{g}"] = (p, t["m"], np.sum(rest ** 2))
    if set(prog["dense"]) != set(ref["dense"]):
        raise ValueError(f"tower leaves differ: {sorted(set(prog['dense']) ^ set(ref['dense']))}")
    parts["dense"] = (np.concatenate([np.ravel(prog["dense"][k]) for k in sorted(ref["dense"])]),
                      np.concatenate([np.ravel(ref["dense"][k]) for k in sorted(ref["dense"])]),
                      0.0)
    gap, flip = (0.0, ""), (0.0, "")
    for name, (p, r, extra) in parts.items():
        p, r = p.astype(np.float64), r.astype(np.float64)
        g = _ratio(np.sum((p - r) ** 2) + extra, np.sum(r ** 2))
        nz = r != 0
        f = float(np.sum((np.sign(p) != np.sign(r)) & nz) / max(1, np.sum(nz)))
        gap, flip = max(gap, (g, name)), max(flip, (f, name))
    return gap, flip


def _dense_gap(dense: dict, ref_state: dict) -> float:
    ref, ref0 = ref_state["dense"], ref_state["dense0"]
    diff = sum(np.sum((np.asarray(dense[k], np.float64) - v) ** 2) for k, v in ref.items())
    moved = sum(np.sum((np.asarray(v, np.float64) - ref0[k]) ** 2) for k, v in ref.items())
    return float(np.sqrt(diff / moved)) if moved > 0 else float("inf")


def _worst(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: program {sorted(set(prog) - set(ref))}, "
                         f"reference {sorted(set(ref) - set(prog))}")
    floor = statistics.median(ref.values())
    worst, leaf = 0.0, ""
    for k in sorted(ref):
        if keep is not None and k not in keep:
            continue
        gap = abs(prog[k] - ref[k]) / max(ref[k], floor)
        if not np.isfinite(gap):
            gap = float("inf")
        if gap >= worst:
            worst, leaf = gap, k
    return worst, leaf


def numbers(prog: dict, ref: dict, init_rows=None) -> dict:
    """{number: (value, worst leaf or step)} of one run. ``row_gap`` and
    ``dense_gap`` need the program's ``rows`` and ``dense``, the reference's
    ``state`` and ``init_rows(keys, dim)`` on the host; ``moment_gap`` and
    ``sign_flip_share`` need both sides' ``m1``."""
    steps = [abs(a - b) for a, b in zip(prog["losses"], ref["losses"])]
    if len(prog["losses"]) != len(ref["losses"]):
        steps.append(float("inf"))
    k = int(np.argmax(steps))
    med = statistics.median(ref["grad_norms"].values())
    keep = {n for n, g in ref["grad_norms"].items() if g >= TINY * med}
    out = {
        "loss_gap": (float(steps[k]) if np.isfinite(steps[k]) else float("inf"), f"step {k + 1}"),
        "grad_gap": _worst(prog["grad_norms"], ref["grad_norms"]),
        "change_gap": _worst(prog["change_norms"], ref["change_norms"], keep),
    }
    if "rows" in prog and "state" in ref:
        out["row_gap"] = _row_gap(prog["rows"], ref["state"]["tables"], init_rows)
        out["dense_gap"] = (_dense_gap(prog["dense"], ref["state"]), "dense")
    if "m1" in prog and "m1" in ref:
        out["moment_gap"], out["sign_flip_share"] = _moment_gap(prog["m1"], ref["m1"])
    return out


def judge(nums: dict, limits: dict | None) -> tuple[bool, dict]:
    """(correct, {number: {"value", "limit"}}): correct when every number
    the limits name is present and within its limit; no limits, not
    correct. Numbers the limits do not name are reported with none."""
    limits = {k: v for k, v in (limits or {}).items() if k in NUMBERS}
    ok = bool(limits)
    out = {}
    for name in NUMBERS:
        value = nums[name][0] if name in nums else None
        if name in limits:
            ok = ok and value is not None and value <= limits[name]
        # a value that is not finite prints as null (JSON has no infinity)
        finite = value is not None and bool(np.isfinite(value))
        out[name] = {"value": value if finite else None, "limit": limits.get(name)}
    return ok, out
