"""The benchmark's own yardstick: specification loading, traffic
arithmetic, peaks, operation counts and trace reduction. Nothing here
imports the program under test."""
