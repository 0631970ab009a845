// The socket two native ranks of one host use between them: a Unix-domain
// stream socket in Linux's abstract namespace, named after the TCP port of
// the rank that listens ("\0adlb_tpu.<port>"). Shared by libadlb.cpp and
// serverd.cpp so that both ends spell the name one way.
//
// Why: every rank of a one-host world, and most peers of a rank in a
// multi-host one, is a process on the same machine, and a loopback TCP
// round trip pays a TCP state machine, an IP layer and an acknowledgement
// in each direction (where the host's network stack runs in user space, as
// under gVisor, that is most of a small frame's round trip). MPI, which the
// reference runs on, carries intra-node messages over shared memory for the
// same reason. Both families are SOCK_STREAM: ordered, reliable, EOF on
// close, so the framing and the reactors above are the same code.
//
// The name: abstract, so there is no file to unlink and it dies with the
// process; scoped to the network namespace exactly as the port is, and
// unique exactly when the port is, so two worlds on one host cannot meet. A
// rank binds it BEFORE its TCP listener becomes reachable, so "TCP
// accepted" implies "Unix was there to try".
//
// Nothing selects it. A sender tries it when the address map says the
// destination is on its own host (the same host string as its own entry,
// or a loopback address) and falls back to TCP when nobody listens there: a
// Python rank (TcpEndpoint: the balancer sidecar, the debug server, Python
// app ranks and servers of a mixed world) has no such listener, and a peer
// in another network namespace cannot be seen. Other hosts get TCP.
//
// Also here, because both ends share it: how long a rank that awaits a frame
// looks for it before it sleeps (poll_budget_s). A frame's sender is, as a
// rule, microseconds away (a server answers a put within some 10 us of
// reading it, a synchronous client's next request follows its answer by
// less), and a rank that went to sleep pays the host a wake-up for it:
// tens of microseconds where system calls are answered by a user-space
// kernel. So the client's wait for an answer to its own request
// (libadlb.cpp wait_for) and the daemon's wait after a turn that carried
// traffic (serverd.cpp Endpoint::recv) first take what is there without
// blocking, again and again for this long, and sleep only when nothing
// came. The value is transport_shm.py's (_SPIN_S, the Python plane's ring
// poll before it parks on its doorbell), and as there it is 0 on a
// single-core host, where polling only takes the sender's time slice.
// Nothing selects it; what adapts is when a rank enters the phase.

#ifndef ADLB_TPU_HOSTSOCK_HPP
#define ADLB_TPU_HOSTSOCK_HPP

#include <sys/socket.h>
#include <sys/un.h>
#include <unistd.h>

#include <cerrno>
#include <cstddef>
#include <cstdio>
#include <cstring>
#include <string>

namespace hostsock {

// Seconds of CLOCK_MONOTONIC a rank polls for an awaited frame before it
// sleeps (above); 0 means it sleeps at once.
inline double poll_budget_s() {
  static const double s = sysconf(_SC_NPROCESSORS_ONLN) > 1 ? 50e-6 : 0.0;
  return s;
}

inline socklen_t unix_name(int port, sockaddr_un* sa) {
  std::memset(sa, 0, sizeof *sa);
  sa->sun_family = AF_UNIX;
  // sun_path[0] stays NUL: the abstract namespace
  int n = std::snprintf(sa->sun_path + 1, sizeof sa->sun_path - 1,
                        "adlb_tpu.%d", port);
  return socklen_t(offsetof(sockaddr_un, sun_path) + 1 + n);
}

// A non-blocking listener on the name of `port`, or -1 with errno set.
// EADDRINUSE means another live process owns the port's name, hence the
// port: fatal to the caller, as a taken TCP port is. Any other failure means
// the platform has no such socket: the rank goes on with TCP alone, and
// peers that try the name find nobody there.
inline int listen_unix(int port, int backlog) {
  int fd = socket(AF_UNIX, SOCK_STREAM | SOCK_NONBLOCK, 0);
  if (fd < 0) return -1;
  sockaddr_un sa;
  socklen_t len = unix_name(port, &sa);
  if (bind(fd, (sockaddr*)&sa, len) != 0 || listen(fd, backlog) != 0) {
    int e = errno;
    close(fd);
    errno = e;
    return -1;
  }
  return fd;
}

// A connection to whoever listens on the name of `port`; -1 when nobody
// does (ECONNREFUSED) or the family is not to be had.
inline int connect_unix(int port) {
  int fd = socket(AF_UNIX, SOCK_STREAM, 0);
  if (fd < 0) return -1;
  sockaddr_un sa;
  socklen_t len = unix_name(port, &sa);
  if (connect(fd, (sockaddr*)&sa, len) != 0) {
    close(fd);
    return -1;
  }
  return fd;
}

// Is `host` (an address-map entry) the machine of the rank whose own entry
// is `self`? Judged from the strings alone, no lookup.
inline bool same_host(const std::string& host, const std::string& self) {
  return host == self || host.compare(0, 4, "127.") == 0 ||
         host == "localhost" || host == "::1";
}

}  // namespace hostsock

#endif
