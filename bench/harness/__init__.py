"""The benchmark's yardstick: generators, reference, trace reduction and
work counting. Nothing here is imported by the program under test."""
