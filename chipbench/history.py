"""The judging rule of a mix whose query kinds write.

A query kind that sets `WRITES = True` commits one transaction a request
(`chipbench/client.py`), and adds `apply(model, params, key, answer)`,
which applies one committed write to the plain model with the uids the
server assigned (`answer` is what its `parse` made of the reply).

The history is every write the run committed, warm-up and window, in
commit-timestamp order; state k is the plain model with its first k
writes applied. A read may have seen the states [lo, hi]:

  lo  the first state that holds every write answered before the read
      was sent;
  hi  the last state before the first write sent after the read was
      answered (the attempt that committed: an aborted one wrote nothing).

A read is correct iff its kind's own `check` reads `wrong_answers` 0 at
some state of that range. That is the guarantee upstream Dgraph states,
reads linearizable under snapshot isolation: a read sees every write
acknowledged before it was sent, no write sent after it was answered,
and what it sees is a prefix of the commit order. A read with lo > hi
breaks the order itself. A read that matches no state reports its
numbers at lo.

The states are walked forwards once, with no undo, on a copy of the
model: at each, only the reads whose range holds it and that have not
matched yet are judged, kind by kind in one `check` each, which gives
one number per answer. `apply` changes the model in place, so it drops
whatever a kind keeps computed from the model. A mix with no writing
kind has one state, and `run.numbers_of` judges it as it always has.

The control (`chipbench/control.py`) puts in each read's place its
kind's `control` taken at state lo - 1: a stale read, the reference one
acknowledged write behind. A writing kind needs no `control`: the
history stands as the program made it.

Nothing of the program is imported here."""

from __future__ import annotations

import bisect
import copy


def writing(kinds) -> bool:
    return any(getattr(k, "WRITES", False) for k in kinds)


def ranges(reads: list, writes: list) -> list:
    """[(lo, hi)] of each read, against `writes` in commit order."""
    n = len(writes)
    by_done = sorted(range(n), key=lambda i: writes[i]["done"])
    done_at = [writes[i]["done"] for i in by_done]
    lo_upto = [0]  # state that holds the first j writes answered
    for i in by_done:
        lo_upto.append(max(lo_upto[-1], i + 1))
    by_sent = sorted(range(n), key=lambda i: writes[i]["attempt_sent"])
    sent_at = [writes[i]["attempt_sent"] for i in by_sent]
    hi_from = [n] * (n + 1)  # state before the writes sent j-th or later
    for j in range(n - 1, -1, -1):
        hi_from[j] = min(hi_from[j + 1], by_sent[j])
    return [(lo_upto[bisect.bisect_left(done_at, r["sent"])],
             hi_from[bisect.bisect_right(sent_at, r["done"])])
            for r in reads]


def _per_answer(got: dict, count: int) -> list:
    """`check`'s {name: list} of a batch as one {name: number} an
    answer; a list of no numbers gives nothing."""
    rows = [{} for _ in range(count)]
    for name, vals in got.items():
        if len(vals) not in (0, count):
            raise ValueError(f"{name}: {len(vals)} numbers for {count} "
                             "answers: a writing mix takes numbers per answer")
        for row, v in zip(rows, vals):
            row[name] = v
    return rows


def numbers(mix: dict, kinds: list, model, reads: list, writes: list,
            stale: bool = False) -> dict:
    """{name: [one number per read]}: each read's numbers at the state it
    matched, or at lo, kind by kind in the mix's order and in the order
    of `reads` within a kind, as `run.numbers_of` gives them; and where
    the mix writes, the harness's own: `writes_committed` and
    `write_retries` (one a write), `reads_changed_by_writes` (the read
    matched a state whose reference differs from state 0's) and
    `order_violations` (lo > hi), one a read, and what each writing
    kind's `check` says of its writes at the last state. `writes` is the
    history, in commit order; `stale` answers each read by its kind's
    `control` at state lo - 1 (the control)."""
    if writes:  # `apply` changes the model in place
        model = copy.deepcopy(model)
    spans = ranges(reads, writes)
    answers = [r["answer"] for r in reads]
    first, matched, wrong0, names = {}, {}, {}, set()

    def by_kind(batch: list):
        for ki, k in enumerate(mix["kinds"]):
            mine = [i for i in batch if reads[i]["kind"] == ki]
            if mine:
                yield kinds[ki], k["params"], mine

    def judged(s: int, batch: list) -> None:
        for kind, params, mine in by_kind(batch):
            got = kind.check(model, params, [reads[i]["key"] for i in mine],
                             [answers[i] for i in mine], None)
            names.update(got)
            for i, row in zip(mine, _per_answer(got, len(mine))):
                wrong = row["wrong_answers"] != 0
                lo, hi = spans[i]
                if s == 0:
                    wrong0[i] = wrong
                if s == lo:
                    first[i] = row
                if lo <= s <= hi and not wrong:
                    matched[i] = (s, row)

    behind: dict = {}  # the state each read's stale answer is taken at
    for i, (lo, _) in enumerate(spans):
        behind.setdefault(max(lo - 1, 0), []).append(i)
    order = sorted(range(len(reads)), key=lambda i: spans[i][0])
    active, nxt = [], 0
    for s in range(len(writes) + 1):
        if s:
            w = writes[s - 1]
            ki = w["kind"]
            kinds[ki].apply(model, mix["kinds"][ki]["params"], w["key"],
                            w["answer"])
        if stale:
            for kind, params, mine in by_kind(behind.get(s, [])):
                got, _ = kind.control(model, params,
                                      [reads[i]["key"] for i in mine])
                for i, a in zip(mine, got):
                    answers[i] = a
        while nxt < len(order) and spans[order[nxt]][0] == s:
            active.append(order[nxt])
            nxt += 1
        judged(s, list(range(len(reads))) if s == 0 else active)
        active = [i for i in active
                  if i not in matched and s < max(spans[i])]

    out: dict = {}
    for ki in range(len(mix["kinds"])):
        for i in range(len(reads)):
            if reads[i]["kind"] == ki:
                row = matched[i][1] if i in matched else first[i]
                for name, v in row.items():
                    out.setdefault(name, []).append(v)
    for name in names:  # a list of no numbers stays a name, as in `check`
        out.setdefault(name, [])
    if not writing(kinds):
        return out
    for ki, k in enumerate(mix["kinds"]):
        mine = [w for w in writes if w["kind"] == ki]
        if getattr(kinds[ki], "WRITES", False) and mine:
            got = kinds[ki].check(model, k["params"], [w["key"] for w in mine],
                                  [w["answer"] for w in mine], None)
            for name, vals in got.items():
                out.setdefault(name, []).extend(vals)
    out["writes_committed"] = [1.0] * len(writes)
    out["write_retries"] = [float(w["retries"]) for w in writes]
    out["reads_changed_by_writes"] = [
        float(i in matched and matched[i][0] > 0 and wrong0[i])
        for i in range(len(reads))]
    out["order_violations"] = [float(lo > hi) for lo, hi in spans]
    return out
