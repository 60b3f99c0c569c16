"""One comparison with the plain reference a file: a configuration names
its check (``"check"``), and the harness imports ``benchmark.checks.<name>``.

``sample(requests, t0, t1, mix, rng)`` runs in the JAX-free parent once
the window has closed: what of the window's output is compared, as a
JSON-able dict. ``numbers(job, control)`` runs in a child of its own once
the server has gone: each number that is compared, under the name the
configuration's ``limits`` give its limit by. With ``control`` the
lower-precision reference stands in the program's place, and the same
numbers have to come out over their limits. A new kind of model is a new
file here, with its plain reference."""
