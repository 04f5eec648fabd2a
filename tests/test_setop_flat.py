"""`intersect#shared` / `difference#shared` in the flat form
(query/dispatch.py run_rows_vs_one): one membership over the level's
ids, no stack of rows. The jitted path on the CPU backend (`_FORCE_DEVICE`)
against numpy, row for row, through the list form and the ragged form."""

import numpy as np
import pytest

from dgraph_tpu.query import dispatch, ragged
from dgraph_tpu.utils import observe
from dgraph_tpu.utils.observe import METRICS, TRACER

HI = np.uint64(7) << np.uint64(32)
REF = {"intersect": np.intersect1d, "difference": np.setdiff1d}


def _ids(rng, n, lim=1 << 16):
    return np.unique(rng.integers(1, lim, n + n // 2 + 1, dtype=np.uint64))[:n]


def _rows_totalling(rng, total):
    lens = np.full(37, total // 37)
    lens[: total % 37] += 1
    return [_ids(rng, int(n)) for n in lens]


def _shape(name: str):
    """(rows, b, on_device: does the flat program run)."""
    rng = np.random.default_rng(len(name))
    b = _ids(rng, 7900)
    if name == "hub":
        rows = [_ids(rng, int(n)) for n in rng.integers(0, 90, 1500)]
        rows.insert(700, _ids(rng, 600))
    elif name == "empty_ends":
        rows = [_ids(rng, 0), _ids(rng, 40), _ids(rng, 0), _ids(rng, 3),
                _ids(rng, 0)]
    elif name == "all_empty":
        rows = [_ids(rng, 0)] * 6
    elif name == "empty_b":
        rows, b = [_ids(rng, 50) for _ in range(9)], _ids(rng, 0)
    elif name in ("uint32_max", "uint32_max_not_in_b"):
        # the padding's own value is a legal id: validity is by index
        top = np.uint64(0xFFFFFFFF)
        rows = [_ids(rng, 30), np.append(_ids(rng, 30), top), _ids(rng, 5)]
        if name == "uint32_max":
            b = np.append(b, top)
    elif name == "pow2":
        rows = _rows_totalling(rng, 4096)
    elif name == "pow2_plus_1":
        rows = _rows_totalling(rng, 4097)
    elif name == "high_segment":
        rows, b = [r + HI for r in _rows_totalling(rng, 900)], b + HI
    elif name == "other_segment":
        rows = [_ids(rng, 50), _ids(rng, 60) + HI, _ids(rng, 0)]
    elif name == "row_across_segments":
        rows = [_ids(rng, 50), np.concatenate([_ids(rng, 9), _ids(rng, 9) + HI])]
        b = np.concatenate([b, b[:100] + HI])
    else:
        raise ValueError(name)
    return rows, b, name not in ("other_segment", "row_across_segments")


SHAPES = ("hub", "empty_ends", "all_empty", "empty_b", "uint32_max",
          "uint32_max_not_in_b", "pow2", "pow2_plus_1", "high_segment",
          "other_segment", "row_across_segments")


@pytest.fixture
def on_device(monkeypatch):
    monkeypatch.setattr(dispatch, "_FORCE_DEVICE", True)
    monkeypatch.setattr(observe, "_DETAIL_EVERY_S", 0.0)
    return dispatch.SetOpDispatcher()


def _launched(root) -> list:
    return [sp["attrs"]["family"] for sp in TRACER.trace_spans(root.trace_id)
            if sp["name"] == "setop.launch"]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("op", ["intersect", "difference"])
def test_flat_path_matches_numpy(on_device, op, shape):
    d = on_device
    rows, b, flat_runs = _shape(shape)
    want = [REF[op](r, b) for r in rows]
    with TRACER.span("process") as root:
        got = d.run_rows_vs_one(op, rows, b)
    assert len(got) == len(rows)
    for g, w in zip(got, want):
        assert g.dtype == np.uint64 and np.array_equal(g, w)
    # one flat program, or (ids in more than one hi-32 segment) the pair
    # buckets: never the `#shared` family for those
    launched = _launched(root)
    assert launched == [op + "#shared"] if flat_runs else (
        launched and set(launched) == {op})

    flat, offs = ragged.pack_rows(rows)
    out, out_offs = d.run_rows_vs_one_ragged(op, flat, offs, b)
    assert out.dtype == np.uint64 and len(out_offs) == len(rows) + 1
    for i, (g, w) in enumerate(zip(got, want)):
        assert np.array_equal(out[out_offs[i]: out_offs[i + 1]], w)
        assert np.array_equal(g, out[out_offs[i]: out_offs[i + 1]])


@pytest.mark.parametrize("op", ["intersect", "difference"])
def test_the_program_is_given_at_most_four_times_the_ids(on_device, op):
    """What `setop.pad` says it hands the device: `padded <= 4 x ids +
    pb` (the flat ids' bucket is a power of four), where the stack
    gave rows x (widest row + pb): 2,048 x 9,216 for this level. The
    counters say the same over a process."""
    d = on_device
    rows, b, _ = _shape("hub")
    total = sum(len(r) for r in rows)
    before = {k: METRICS.value(k) for k in
              ("device_real_ids_total", "device_padded_ids_total")}
    with TRACER.span("process") as root:
        flat, offs = ragged.pack_rows(rows)
        d.run_rows_vs_one_ragged(op, flat, offs, b)
    (pad,) = [sp["attrs"] for sp in TRACER.trace_spans(root.trace_id)
              if sp["name"] == "setop.pad"]
    assert pad["family"] == op + "#shared" and pad["rows"] == len(rows)
    assert pad["ids"] == total + len(b)
    assert pad["pb"] == dispatch._pow2(len(b))
    assert pad["padded"] == pad["pa"] + pad["pb"]
    assert pad["pa"] == dispatch._pow4(total)
    assert pad["padded"] <= 4 * pad["ids"] + pad["pb"]
    stack = dispatch._pow2(len(rows)) * (dispatch._pow2(600) + pad["pb"])
    assert stack == 2048 * 9216 and pad["padded"] * 50 < stack
    assert METRICS.value("device_real_ids_total") - before[
        "device_real_ids_total"] == pad["ids"]
    assert METRICS.value("device_padded_ids_total") - before[
        "device_padded_ids_total"] == pad["padded"]


@pytest.mark.parametrize("variant", ["pairs", "chain", "union_rows"])
def test_every_family_says_what_it_pads(on_device, variant):
    """`ids` and `padded` ride on `setop.pad` in the stacked families
    too, a stack counted with its per-row copy of the second operand."""
    d = on_device
    rng = np.random.default_rng(3)
    sets = [_ids(rng, n) for n in (300, 400, 500, 40)]
    with TRACER.span("process") as root:
        if variant == "pairs":
            d.run_pairs("intersect", [(sets[0], sets[2]), (sets[1], sets[2])])
            ids, padded = 300 + 400 + 2 * 500, 8 * (512 + 512)
        elif variant == "chain":
            d.run_chain("intersect", sets[:3])
            ids, padded = 300 + 400 + 500, 3 * 512
        else:
            got = d.run_rows_vs_one("union", sets[:2], sets[3])
            for g, r in zip(got, sets):
                assert np.array_equal(g, np.union1d(r, sets[3]))
            ids, padded = 300 + 400 + 40, 8 * (512 + 64)
    pads = [sp["attrs"] for sp in TRACER.trace_spans(root.trace_id)
            if sp["name"] == "setop.pad" and "ids" in sp["attrs"]]
    assert [(p["ids"], p["padded"]) for p in pads] == [(ids, padded)]


def _never(what):
    def never(*_a, **_kw):
        raise AssertionError(what)
    return never


def test_flat_path_splits_and_joins_no_row(on_device, monkeypatch):
    """The hi-32 test is one pass over the level's ids: no
    `split_segments` / `join_segments` a row on the way in or out, in
    the list form or the ragged form; and the ragged form is neither
    cut into row views nor packed again."""
    never = _never("a per-row segment split on the flat path")
    monkeypatch.setattr(dispatch, "split_segments", never)
    monkeypatch.setattr(dispatch, "join_segments", never)
    rows, b, _ = _shape("high_segment")
    flat, offs = ragged.pack_rows(rows)
    got = {op: on_device.run_rows_vs_one(op, rows, b) for op in REF}
    monkeypatch.setattr(ragged, "row_views", _never("a level cut into rows"))
    monkeypatch.setattr(ragged, "pack_rows", _never("a level packed again"))
    for op in REF:
        out, out_offs = on_device.run_rows_vs_one_ragged(op, flat, offs, b)
        assert np.array_equal(out, np.concatenate(got[op]))
        assert np.array_equal(np.diff(out_offs), [len(g) for g in got[op]])
        assert np.array_equal(got[op][3], REF[op](rows[3], b))


DOOR = {form: f'setop_door_total{{form="{form}"}}'
        for form in ("ragged", "rows")}


@pytest.mark.parametrize("shape", [s for s in SHAPES if _shape(s)[2]])
@pytest.mark.parametrize("op", ["intersect", "difference"])
def test_ragged_form_goes_through_the_door_as_it_lies(
        on_device, monkeypatch, op, shape):
    """On the device path a level reaches the door as (flat, offs) and
    comes back so: no `row_views` on the way in, no `pack_rows` on the
    way out, the list form's answers and numpy's row for row, and one
    `setop_door_total{form="ragged"}` for each call that reaches the
    door (a list of rows counts under `rows`)."""
    d = on_device
    rows, b, _ = _shape(shape)
    before = {f: METRICS.value(name) for f, name in DOOR.items()}
    want = d.run_rows_vs_one(op, rows, b)
    assert METRICS.value(DOOR["rows"]) - before["rows"] == 1
    flat, offs = ragged.pack_rows(rows)
    monkeypatch.setattr(ragged, "row_views", _never("a level cut into rows"))
    monkeypatch.setattr(ragged, "pack_rows", _never("a level packed again"))
    out, out_offs = d.run_rows_vs_one_ragged(op, flat, offs, b)
    assert METRICS.value(DOOR["ragged"]) - before["ragged"] == int(
        bool(flat.size and b.size))
    assert METRICS.value(DOOR["rows"]) - before["rows"] == 1
    assert out.dtype == np.uint64 and out_offs.dtype == np.int64
    assert np.array_equal(np.diff(out_offs), [len(w) for w in want])
    for i, (r, w) in enumerate(zip(rows, want)):
        assert np.array_equal(w, REF[op](r, b))
        assert np.array_equal(out[out_offs[i]: out_offs[i + 1]], w)


@pytest.mark.parametrize("cut", [0, 2], ids=["as_is", "halved"])
@pytest.mark.parametrize("op", ["intersect", "difference"])
def test_a_door_wrapped_to_return_rows_still_yields_the_level(
        on_device, op, cut):
    """A wrapper around the door that hands back a plain list of rows,
    as a planted fault does, is packed once by the ragged caller: its
    rows, and whatever it did to them, are the level that comes out."""
    d = on_device
    rows, b, _ = _shape("hub")
    orig = d.run_rows_vs_one

    def wrapped(op, rows, b, *a, **kw):
        return [np.asarray(r)[len(r) // cut if cut else 0:]
                for r in orig(op, rows, b, *a, **kw)]

    d.run_rows_vs_one = wrapped
    flat, offs = ragged.pack_rows(rows)
    out, out_offs = d.run_rows_vs_one_ragged(op, flat, offs, b)
    assert out.dtype == np.uint64 and len(out_offs) == len(rows) + 1
    for i, r in enumerate(rows):
        w = REF[op](r, b)
        w = w[len(w) // cut:] if cut else w
        assert np.array_equal(out[out_offs[i]: out_offs[i + 1]], w)


@pytest.mark.parametrize("n, want", [
    (0, 16), (1, 16), (16, 16), (17, 64), (4096, 4096), (4097, 16384),
    (123_000, 262_144), (131_073, 262_144), (151_104, 262_144),
    (262_145, 1 << 20)])
def test_pow4_is_the_next_power_of_four(n, want):
    """One bucket holds every third level of `snb.ic1` (123k-151k ids)."""
    assert dispatch._pow4(n) == want
    assert n <= want and (n <= 16 or want < 4 * n)
