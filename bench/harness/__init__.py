"""The benchmark's own machinery: cell loading, data and traffic
generation, the timed window, and the reduction of traces to metrics.

Nothing here is part of the system under test; later changes to the
program cannot move it."""
