"""Plain reference for `statements/ssb_q4_1.sql` (SSB Q4.1): its
description in `references/ssb.py`, whose one function answers all
thirteen."""

from .ssb import reference_for

build, compare, tolerance = reference_for("q4_1")
