"""The bytes the compiled step wants for its temporaries, in GB: ``temp`` of
the ``memory_analysis()`` of the program the executor built, as its record
holds it (``telemetry.programs()``: ``memory``). ``memory_peak_bytes`` leaves
them out; arguments plus these is what has to fit the chip. Program record
(``programs.memory``)."""
from lib import programs


def read(run):
    temp = programs.memory("temp")
    return None if temp is None else temp / 1e9
