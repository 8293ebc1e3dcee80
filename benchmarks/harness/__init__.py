"""The benchmark's own yardstick: traffic generation, the reduction from
traces, spans and counters to metrics, the table of peaks, the shape
functions for operations and bytes, and the comparison that decides
``correct``. Nothing here imports the program under test except where a
function says so; later PRs may add files beside these and edit none."""
