"""Query kind `ic1_counted`: LDBC SNB Interactive complex read 1 exactly
as kind `ic1` sends, parses and compares it (`chipbench/queries/ic1.py`,
which `snb.ic1` runs and no later PR may edit), with one number more:
`compared_ic1`, so that a mix of several kinds can ask that complex
read 1 was among the answers held to the model."""

from __future__ import annotations

from chipbench.queries import ic1
from chipbench.queries.ic1 import (  # noqa: F401  (the kind's interface)
    control, parse, reference, request, shape)


def check(model, params: dict, keys: list, answers: list,
          captured=None) -> dict:
    out = ic1.check(model, params, keys, answers, captured)
    return dict(out, compared_ic1=out["answers_compared"])
