"""The benchmark's yardstick: traffic, runners, reductions, peaks, costs."""
