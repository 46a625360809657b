"""Autotune CSV schema — the single source of truth for the column layout.

Three consumers resolve here so the next arm can't silently skew the
parse (ISSUE 18 satellite):

  * the C++ writer's header literal in ``csrc/autotune.cc`` (checked
    against this table by the hvdlint ``arm-stats`` rule),
  * the ``tests/workers/autotune_worker.py`` log assertions,
  * operator tooling slicing columns by name.

Layout: ``sample`` then the numeric point, then one column per
categorical dim in arm-bit order (``ARM_COLUMNS``), then the recorded
context fields, then the v2 search context (``bracket`` = probe/h<r>/gp
phase label, ``profile`` = adoption-ladder outcome), then the score.
"""

COLUMNS = (
    "sample",
    "fusion_kb",
    "cycle_ms",
    "cache",
    "hier",
    "zerocopy",
    "pipeline",
    "shm",
    "bucket",
    "compress",
    "wire",
    "alltoall",
    "affinity",
    "schedule",
    "bracket",
    "profile",
    "score_mbps",
)

HEADER = ",".join(COLUMNS)

# The categorical arm dims, in csrc/autotune.h AutotuneDim (== arm bit)
# order. Every entry has a tuned_<dim> ResponseList field, an init_<dim> /
# can_toggle_<dim> AutotuneConfig field, and a <dim>_stats() surface —
# cross-checked by tools/hvdlint.py check_arm_stats.
ARM_COLUMNS = COLUMNS[COLUMNS.index("cache"):COLUMNS.index("alltoall") + 1]

# Values the `profile` column (and autotune_stats()["profile"]) can take:
# "-" = HVD_AUTOTUNE_PROFILE_DIR unset, then the adoption ladder.
PROFILE_STATES = ("-", "fresh", "near", "adopted", "corrupt")


def col(name):
    """Column index for a schema name (raises ValueError if unknown)."""
    return COLUMNS.index(name)


def split_row(line):
    """Split one CSV data row into a dict keyed by column name."""
    parts = line.split(",")
    if len(parts) != len(COLUMNS):
        raise ValueError(f"row has {len(parts)} fields, "
                         f"schema has {len(COLUMNS)}: {line!r}")
    return dict(zip(COLUMNS, parts))
