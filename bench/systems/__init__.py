"""Adapters, one per kind of system under test, named by a configuration's
`system` key.  Each module defines:

  CODES          op names of the traffic mixes -> the program's op codes
                 (with "IDLE", the code of a lane that does nothing);
  WRITES         the op names that change the state;
  TINY           configuration overrides that cut a cell to a CPU test's
                 size;
  entry          the call the window times, `entry(spec, state, ops) ->
                 (state', ...)`: `ops` has a `kind` array and `_replace`,
                 and one of the other results has `value`, `success` and
                 `_replace`.  The CPU tests plant their faults by wrapping
                 it, so a Cell calls it by its module name;
  Cell           `Cell(config, traffic, seed, devices)`: set-up on the
                 cell's devices, one batch, read-back after the window, and
                 the check against the reference, {name: (value, limit)},
                 among them `op_mismatches`, the ops whose answer is not
                 the reference's;
  Control        `Control(config, traffic, seed, devices, weaken)`: a Cell
                 with a weakened reference in the program's place;

and, where its state has records that no batch names, `change_record(
state, record, field)`: the state with that record's "data" or "version"
changed, a fault that only the check of untouched records (a check whose
name starts with "untouched") can catch.
"""
