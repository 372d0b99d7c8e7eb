"""Load loops, one file per loop that a traffic mix names; each defines
a function of the loop's name that takes the run and returns its
outcome."""
