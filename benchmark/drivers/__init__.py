"""One load driver a file: a mix names its driver (``"driver"``), and
`run.py` imports ``benchmark.drivers.<name>`` and drives its ``Load``
(``warm()``, ``ramp()``, ``hold(until_ns)``, ``close()``, ``requests``,
``errors``). A new kind of load is a new file here."""
