"""One file per runner, imported by the name in the traffic file.  A runner
drives the system under test: ``setup(config, traffic, seed, clock)`` builds
the state once, ``step(state)`` does one whole unit of timed work,
``work(state)`` gives shapes and counts to the roofline functions,
``floor(state, peak)`` the least seconds of one step, and
``check(state, traffic)`` frees the program's state and compares what the
timed path produced with the plain reference."""
