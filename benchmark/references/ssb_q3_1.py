"""Plain reference for `statements/ssb_q3_1.sql` (SSB Q3.1): its
description in `references/ssb.py`, whose one function answers all
thirteen."""

from .ssb import reference_for

build, compare, tolerance = reference_for("q3_1")
