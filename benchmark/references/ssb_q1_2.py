"""Plain reference for `statements/ssb_q1_2.sql` (SSB Q1.2): its
description in `references/ssb.py`, whose one function answers all
thirteen."""

from .ssb import reference_for

build, compare, tolerance = reference_for("q1_2")
