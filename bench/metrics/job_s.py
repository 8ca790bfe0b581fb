"""Wall seconds per job: the window (first job's start to the last
job's result ready on the device) over the jobs run in it."""
from harness.readers import jobs


def read(run):
    done = jobs(run)
    if not done:
        return None
    return (run.window[1] - run.window[0]) / len(done)
