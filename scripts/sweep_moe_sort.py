"""The routed feed-forward's bookkeeping alone, on the chip, at the MoE
cells' shapes.

    python scripts/sweep_moe_sort.py [--iters N] [--out FILE]
        [--forms F,F,...] [N,K,E ...]

The bookkeeping is what ``models/transformer.py::moe_held_experts``
does between the router's ``expert [N, k]`` and the grouped products,
and after them: which held expert's group each padded row belongs to,
the token it reads (``src``), whether it holds an assignment (``live``)
and the row each assignment's result comes back from (``at``).  Forms:
``sort``, the form before ``moe_group_rows`` (built here: ``argsort``
of the keys, ``searchsorted`` over the rows, ``argsort`` of the order),
and ``<place>+<source>``: an assignment's place in the stable order by
key, then each row's source, computed by

- place ``compare``: how many assignments come before it by (key,
  index), one ``[N·k, N·k]`` comparison summed; ``count``: the keys'
  starts plus an exclusive running count of a one-hot ``[N·k, e + 1]``
  down the assignments, read at the key; ``tril``: that running count
  as one lower-triangular product on the MXU (0/1 bfloat16 operands,
  float32 sums: exact);
- source ``match``: the assignment whose place is the row's, one
  ``[rows, N·k]`` comparison; ``inverse``: the order itself (the
  assignment at each place, one ``[N·k, N·k]`` comparison), read at the
  row's place; ``scatter``: each assignment's token
  scattered to its row (a row that holds none reads token 0, not
  ``sort``'s token).

``kept`` is ``transformer.moe_group_rows`` itself, what the program
runs: ``compare``'s places, and the tokens scattered to their rows
with no row's group computed.  Every form is held to ``sort``:
``padded``, ``live``, ``at`` and the live rows' ``src`` equal, and
``src`` equal on every row but where a form scatters.
Times are device times: ONE program runs ``--iters`` calls back to back
in a ``lax.fori_loop`` (the keys rolled by one a call, so no call can
be hoisted), and a call's time is the difference of the host clock
around ``2 --iters`` and ``--iters`` calls over ``--iters``.  Two a
form and shape: the bookkeeping alone (``us``), and with the rows'
gather in and out at a width of 2,048 bfloat16 (``us_gathers``).  The
compiled text's ``while``, ``sort`` and ``scatter`` instructions are
counted beside.  One JSON line a form and shape.  Exits non-zero on any
backend but TPU, or where a form departs from ``sort``.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distkeras_tpu.models import transformer as tfm
from sweep_ssm import device_s

TILE = tfm.GROUP_TILE
WIDTH = 2048
# joyai's step and chunk, then kexaone's (29 and 202 lanes; 512-row chunks)
SHAPES = ((29, 8, 32), (512, 8, 32), (202, 8, 16), (512, 8, 16))


def _rows(key, e, place):
    """What every form shares once it has each assignment's place in
    the stable order by key: the groups' rows, each row's group and the
    place it reads, and each assignment's row."""
    m = key.shape[0]
    sizes = jnp.sum(key[:, None] == jnp.arange(e)[None, :], axis=0,
                    dtype=jnp.int32)
    padded = jnp.maximum(-(-sizes // TILE), 1) * TILE
    first = jnp.cumsum(sizes) - sizes
    base = jnp.cumsum(padded) - padded
    row = jnp.arange(-(-m // TILE) * TILE + e * TILE)
    group = jnp.minimum(jnp.sum(row[:, None] >= (base + padded)[None, :],
                                axis=1, dtype=jnp.int32), e - 1)
    within = row - base[group]
    live = within < sizes[group]
    want = jnp.clip(first[group] + within, 0, m - 1)
    slot = jnp.minimum(key, e - 1)
    at = base[slot] + place - first[slot]
    return padded, live, want, at


def _place_compare(key, e):
    index = jnp.arange(key.shape[0])
    return jnp.sum((key[None, :] < key[:, None])
                   | ((key[None, :] == key[:, None])
                      & (index[None, :] < index[:, None])),
                   axis=1, dtype=jnp.int32)


def _place_by_count(running):
    def place(key, e):
        onehot = key[:, None] == jnp.arange(e + 1)[None, :]
        counts = jnp.sum(onehot, axis=0, dtype=jnp.int32)
        start = jnp.cumsum(counts) - counts
        rank = jnp.take_along_axis(running(onehot), key[:, None],
                                   axis=1)[:, 0]
        return start[key] + rank
    return place


def _exclusive_cumsum(onehot):
    c = onehot.astype(jnp.int32)
    return jnp.cumsum(c, axis=0) - c


def _tril_product(onehot):
    index = jnp.arange(onehot.shape[0])
    before = (index[None, :] < index[:, None]).astype(jnp.bfloat16)
    return jnp.dot(before, onehot.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def _src_match(key, e, k, place, live, want, at):
    index = jnp.arange(key.shape[0])
    return jnp.sum(jnp.where(place[None, :] == want[:, None],
                             index[None, :] // k, 0), axis=1, dtype=jnp.int32)


def _src_inverse(key, e, k, place, live, want, at):
    index = jnp.arange(key.shape[0])
    order = jnp.sum(jnp.where(place[None, :] == index[:, None],
                              index[None, :], 0), axis=1, dtype=jnp.int32)
    return order[want] // k


def _src_scatter(key, e, k, place, live, want, at):
    index = jnp.arange(key.shape[0])
    return jnp.zeros(live.shape, jnp.int32).at[
        jnp.where(key < e, at, live.shape[0])].set(index // k, mode="drop")


def _form(place_of, src_of):
    def form(key, e, k):
        place = place_of(key, e)
        padded, live, want, at = _rows(key, e, place)
        return padded, src_of(key, e, k, place, live, want, at), live, at
    return form


def form_sort(key, e, k):
    m = key.shape[0]
    order = jnp.argsort(key)
    sizes = jnp.sum(key[:, None] == jnp.arange(e)[None, :], axis=0,
                    dtype=jnp.int32)
    padded = jnp.maximum(-(-sizes // TILE), 1) * TILE
    first = jnp.cumsum(sizes) - sizes
    base = jnp.cumsum(padded) - padded
    row = jnp.arange(-(-m // TILE) * TILE + e * TILE)
    group = jnp.minimum(jnp.searchsorted(base + padded, row, side="right"),
                        e - 1)
    within = row - base[group]
    live = within < sizes[group]
    src = order[jnp.clip(first[group] + within, 0, m - 1)] // k
    slot = jnp.minimum(key, e - 1)
    at = base[slot] + jnp.argsort(order) - first[slot]
    return padded, src, live, at


PLACES = {"compare": _place_compare, "count": _place_by_count(
    _exclusive_cumsum), "tril": _place_by_count(_tril_product)}
SOURCES = {"match": _src_match, "inverse": _src_inverse,
           "scatter": _src_scatter}
FORMS = {"sort": form_sort, "kept": tfm.moe_group_rows, **{
    f"{p}+{s}": _form(PLACES[p], SOURCES[s]) for p in PLACES for s in SOURCES}}


def keys(n, k, e, rng):
    """A router's picks seen from one share: k distinct experts of 8e
    a token, those past e absent."""
    picks = np.argsort(rng.random((n, 8 * e)), axis=1)[:, :k]
    return np.minimum(picks, e).reshape(-1).astype(np.int32)


def census(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    return {op: len(re.findall(rf"\b{op}\(", text))
            for op in ("while", "sort", "scatter")}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--out")
    ap.add_argument("--forms", default=",".join(FORMS),
                    help="comma-separated, of: " + ", ".join(FORMS))
    ap.add_argument("shapes", nargs="*",
                    default=[",".join(map(str, s)) for s in SHAPES])
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("sweep_moe_sort: TPU only", file=sys.stderr)
        return 3
    rng = np.random.default_rng(0)
    lines, bad = [], False
    for shape in args.shapes:
        n, k, e = map(int, shape.split(","))
        key = jnp.asarray(keys(n, k, e, rng))
        want = [np.asarray(a) for a in jax.jit(
            form_sort, static_argnums=(1, 2))(key, e, k)]
        h = jnp.asarray(rng.normal(size=(n, WIDTH)), jnp.bfloat16)
        for name in args.forms.split(","):
            form = FORMS[name]
            scatters = name == "kept" or name.endswith("scatter")
            got = [np.asarray(a) for a in jax.jit(
                form, static_argnums=(1, 2))(key, e, k)]
            live = want[2]
            equal = {"padded": bool((got[0] == want[0]).all()),
                     "live": bool((got[2] == live).all()),
                     "at": bool((got[3] == want[3]).all()),
                     "src_live": bool((got[1] == want[1])[live].all()),
                     "src_all": bool((got[1] == want[1]).all())}
            bad |= not all(v for what, v in equal.items()
                           if what != "src_all" or not scatters)

            def alone(c, form=form):
                key, acc = c
                padded, src, live, at = form(key, e, k)
                return jnp.roll(key, 1), (acc + padded.sum() + src.sum()
                                          + live.sum() + at.sum())

            def gathers(c, form=form):
                key, x = c
                _, src, live, at = form(key, e, k)
                rows = jnp.where(live[:, None], x[src], 0)
                y = jnp.where((key < e)[:, None],
                              rows[at].astype(jnp.float32), 0.0)
                x = (y.reshape(n, k, -1).sum(axis=1) / k).astype(x.dtype)
                return jnp.roll(key, 1), x

            # device_s donates its carry: hand it copies
            t, _ = device_s(alone, (jnp.array(key), jnp.int32(0)), args.iters)
            tg, _ = device_s(gathers, (jnp.array(key), jnp.array(h)),
                             args.iters)
            line = {"form": name, "n": n, "k": k, "e": e,
                    "rows": int(want[2].shape[0]), "equal": equal,
                    "us": 1e6 * t, "us_gathers": 1e6 * tg,
                    "compiled": census(lambda key, form=form: form(key, e, k),
                                       key)}
            lines.append(line)
            print(json.dumps(line), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
