"""How ``correct`` is decided for a training cell.

The runner puts seeded weights into the object it is about to time and
drives it through its first steps by the window's own call and feed, on
rows that all differ. From that object's state it keeps (on the host)
each step's loss, the first gradient as the optimizer got it, and the
parameters after the steps. Once the window has closed and the program's
state is freed, the plain float32 reference follows those steps from the
same weights (made by the benchmark, ``weights.py``) on the same rows.
Compared, each against a limit of its own (``check.limits`` of the
configuration's file):

- ``loss_gap``: the largest relative gap of a step's loss;
- ``grad_norm_gap``: the first gradient by the worst leaf:
  |program's norm - reference's norm| over the reference's norm of that
  leaf or of the median leaf, whichever is larger;
- ``grad_rms_gap``: the norm of the difference of the two first
  gradients, all leaves together, over the norm of the reference's.
  Rounding is noise of mean zero, which a mean over the batch (the loss)
  or a leaf's norm all but hides; element by element it shows, so this
  is the number that a lower precision fails;
- ``update_norm_gap``: as ``grad_norm_gap``, for the parameters' change
  after the steps.

The control puts the reference, computed in the precision below the
configuration's, in the program's place: it has to fail a limit. The
reference is found by the name in ``check.kind``.
"""

from __future__ import annotations

import importlib
import statistics
import time

import jax
import jax.numpy as jnp


def named_leaves(tree) -> dict:
    """{"enc0/mlp/wi/kernel": leaf}: the program's flax tree and the
    reference's nested dict name their leaves alike."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(p.key) for p in path if hasattr(p, "key")): leaf
            for path, leaf in leaves}


def _norms(leaves: dict) -> dict:
    """{name: float32 l2 norm}, one device call and one copy back."""
    norms = jax.jit(lambda xs: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in xs
    ])(list(leaves.values()))
    return dict(zip(leaves, map(float, jax.device_get(norms))))


def worst_leaf_gap(prog: dict, ref: dict, skip=()) -> tuple:
    """(gap, leaf) of the leaf whose norms lie farthest apart."""
    floor = statistics.median(ref.values())
    return max((abs(prog[k] - ref[k]) / max(ref[k], floor), k)
               for k in ref if k not in skip)


def dead_leaves(grad_norms: dict) -> set:
    """Leaves whose reference gradient is all but zero (under a thousandth
    of the median leaf's): a softmax cannot see a bias on its keys. Adam
    divides a gradient by its own size, so it turns such a leaf's rounding
    into an update as large as any other; the update is compared without
    them."""
    floor = 1e-3 * statistics.median(grad_norms.values())
    return {k for k, v in grad_norms.items() if v < floor}


def rms_gap(prog: dict, ref: dict) -> float:
    """||prog - ref|| / ||ref|| over all leaves together."""
    gap = jax.jit(lambda ps, rs: jnp.sqrt(
        sum(jnp.sum(jnp.square(p.astype(jnp.float32) - r))
            for p, r in zip(ps, rs))
        / sum(jnp.sum(jnp.square(r)) for r in rs)))
    return float(gap([prog[k] for k in ref], list(ref.values())))


def compare(prog: dict, ref: dict, start, limits: dict) -> dict:
    """{number: {"value", "limit", "at", "ok"}} from two sides' readings
    (``losses``, ``grad``, ``params``) and the weights both started from;
    a number that is not finite fails whatever its limit."""
    start = named_leaves(start)
    grads = {side: named_leaves(r["grad"]) for side, r in
             (("prog", prog), ("ref", ref))}
    if set(grads["prog"]) != set(grads["ref"]):
        raise ValueError("program and reference disagree on the leaves: "
                         f"{sorted(set(grads['prog']) ^ set(grads['ref']))[:4]}")
    grad_norms = {side: _norms(g) for side, g in grads.items()}
    update_norms = {
        side: _norms({k: jnp.asarray(v) - start[k]
                      for k, v in named_leaves(r["params"]).items()})
        for side, r in (("prog", prog), ("ref", ref))}
    grad, grad_leaf = worst_leaf_gap(grad_norms["prog"], grad_norms["ref"])
    upd, upd_leaf = worst_leaf_gap(update_norms["prog"], update_norms["ref"],
                                   skip=dead_leaves(grad_norms["ref"]))
    values = {
        "loss_gap": (max(abs(p - r) / abs(r) for p, r in
                         zip(prog["losses"], ref["losses"])),
                     f"{len(ref['losses'])} steps"),
        "grad_norm_gap": (grad, grad_leaf),
        "grad_rms_gap": (rms_gap(grads["prog"], grads["ref"]), "all leaves"),
        "update_norm_gap": (upd, upd_leaf),
    }
    return {k: {"value": v, "limit": limits[k], "at": at,
                "ok": bool(v == v and v <= limits[k])}
            for k, (v, at) in values.items()}


def run(config: dict, seed: int, rows, program: dict | None = None) -> dict:
    """The reference over ``rows`` (one ``(src, tgt)`` a step) from the
    seed's weights, against ``program`` (the readings the runner took from
    the timed object) or, without one, against the control.

    Returns ``{"correct", "numbers", "losses", "seconds"}``; prints every
    number compared beside its limit.
    """
    t0 = time.perf_counter()
    spec = config["check"]
    mod = importlib.import_module(f"chipbench.checks.{spec['kind']}")
    side = "program" if program is not None else "control"
    if program is None:
        program = mod.reference_readings(config, seed, rows,
                                         spec["control_precision"])
    ref = mod.reference_readings(config, seed, rows, "float32")
    numbers = compare(program, ref, mod.weights(config, seed),
                      spec["limits"])
    for name, n in numbers.items():
        print(f"check {side} {name}: {n['value']:.6g} (limit {n['limit']:g})"
              f" {'ok' if n['ok'] else 'FAILED'} at {n['at']}", flush=True)
    return {"correct": all(n["ok"] for n in numbers.values()),
            "numbers": numbers, "losses": {side: program["losses"],
                                           "reference": ref["losses"]},
            "seconds": time.perf_counter() - t0}
