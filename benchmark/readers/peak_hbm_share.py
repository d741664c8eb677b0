"""Peak bytes in use over the device's limit, fullest device, in %."""


def read(run):
    shares = [m["peak_bytes_in_use"] / m["bytes_limit"]
              for m in run["memory"]
              if m and m.get("bytes_limit") and "peak_bytes_in_use" in m]
    return 100.0 * max(shares) if shares else None
