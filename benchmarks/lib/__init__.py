"""The yardstick: what may not move with the program under test."""
