"""Runners: one file per kind of cell, found by the `runner` name in the
cell's file. A runner drives the system under test through its normal entry
points and hands back what it collected; it computes the cell's end-to-end
metrics itself, from its own clock and counts."""
