"""One module per kind of model, ``bench/kinds/<kind>.py``, found by the
``kind`` of a configuration file (``bench.spec.kind``).  A kind holds
everything the harness must know about the model: the rest of ``bench``
only dispatches through these functions, so a new kind is a new file.

    check_registry(cfg, config)   the program's registry entry is the
                                  configuration file's model, or raise
    build_engine(config, traffic, params, program)
                                  the program's ServingEngine
    weight_shapes(config)         (shape tree, dtype, stacked prefix)
    init_leaf(path, key, shape, dtype, stacked)
                                  optional: the initial value of a leaf
                                  that must keep a sign or a range, or
                                  None for the rule of bench/weights.py
    make_inputs(traffic, config, rng)
                                  the run's inputs, a dict
    request(t, uid)               (Request, drive.Rec) of request uid
    warm_up(eng, t)               compile every shape the window uses
    prime(client, t)              set-up work that puts the engine in
                                  the state the window starts from
    capture(eng)                  keep what the timed path hands the
                                  host, for the comparison
    on_output(tick, rec, before, after)
                                  count the work of outputs served
    compare(config, params, t, window, rng, check, controls)
                                  the numbers that decide ``correct``
    tick_flops(config, tick)      operations of one step's work

A new configuration is new files and new entries in ``BENCHMARK.json``;
no file the benchmark already has is edited:

    bench/configs/<config>.json   its sizes, kind, registry name,
                                  source and precision
    bench/kinds/<kind>.py         the functions above, where no kind
                                  serves it yet
    bench/refs/<kind>.py          the plain reference, importing
                                  nothing of the program
    bench/work/<kind>.py          operations and bytes of its steps and
                                  kernel calls
    bench/traffic/<traffic>.json  each mix's parameters, for a loop of
                                  bench/loops/ (or a new loop file)
    bench/limits/<cell>.json      each cell's correctness limits and the
                                  readings they were set from
    bench/metrics/<metric>.py     one reader per new metric

``BENCHMARK.json`` gains an entry under ``configs`` (its file under
``paths``), one under ``workloads`` per cell, and the cell's name in the
``workloads`` list of every metric the cell reports; a new metric is an
entry under ``per_layer`` (or ``end_to_end``) with its own
``workloads``.  A reader takes what it needs from ``bench.run.RunData``:
the window's ``Tick``s carry the program's ``serving.*`` phase seconds
(``phases``) and counter differences (``counters``) of every step, and
the trace (``bench.trace_reduce.Trace``) holds the device ops with their
ProgramOp scopes and the host spans.  A new kernel needs no entry
anywhere: its Pallas calls carry the scope ``<kernel>.<name>`` of the
ProgramOp that emitted them, which names them in the trace, so
``measure.roofline(run, "<kernel>", calls)`` reads its share with the
calls that ``bench/work/<kind>.py`` counts.
"""
