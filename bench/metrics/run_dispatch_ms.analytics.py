"""Host milliseconds per job inside ``Engine.run`` that are not the
device wait the program measures itself (``decision["measured"]
["device_wait_s"]``): resolve, delivery layout, trace and dispatch."""
from harness.readers import jobs


def read(run):
    done = jobs(run)
    if not done:
        return None
    host = [(j.end - j.start) - j.device_wait_s for j in done]
    return 1e3 * sum(host) / len(host)
