"""Traffic drivers: benchmark/drivers/<kind>.py, named by a traffic mix's
"kind". Each has run(run) and drives one cell's window (see harness.Run).
"""
