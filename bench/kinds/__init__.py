"""One module per kind of model, ``bench/kinds/<kind>.py``, found by the
``kind`` of a configuration file (``bench.spec.kind``).  A kind holds
everything the harness must know about the model: the rest of ``bench``
only dispatches through these functions, so a new kind is a new file.

    check_registry(cfg, config)   the program's registry entry is the
                                  configuration file's model, or raise
    build_engine(config, traffic, params, program)
                                  the program's ServingEngine
    weight_shapes(config)         (shape tree, dtype, stacked prefix)
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
"""
