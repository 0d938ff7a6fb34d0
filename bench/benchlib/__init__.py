"""The benchmark's own library: cell lookup, traffic, peaks, trace reduction."""
