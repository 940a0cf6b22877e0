"""Compile events inside the window (x/tracewatch; expected 0)."""


def read(cell, params):
    return cell.compiles_in_window
