"""Adapters, one per kind of system under test, named by a configuration's
`system` key.  Each module defines `CODES` (op names to the program's op
codes), `entry` (the call the window times), `Cell` (set-up, one batch,
read-back after the window, and the check against the reference) and
`Control` (a `Cell` with a weakened reference in the program's place)."""
