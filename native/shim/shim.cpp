// shadow_tpu managed-process shim (LD_PRELOAD).
//
// Role parity with the reference's shim plane (src/lib/shim/shim.c,
// preload_syscall.c, preload_libraries.c): co-opts a real Linux binary into
// the simulation by interposing at the libc API, relaying network/time
// syscalls over a shared-memory channel to the simulator driver, which
// executes them against the device-stepped network and the simulated clock.
//
// Interposition model (differences from the reference, all deliberate):
//   * libc-symbol interposition is the fast path; a seccomp/SIGSYS
//     backstop (reference analog: shim.c:399-463 seccomp filter + SIGSYS
//     trampoline) catches raw syscall instructions that bypass the PLT —
//     statically-linked binaries, libc internals, inline `syscall(2)`.
//     The BPF filter traps only the emulated syscall numbers and allows
//     everything issued from the shim's own gate function, so shim-internal
//     native calls never pay the signal round trip. Disable with
//     SHADOW_TPU_SECCOMP=0.
//     exec is handled as DRIVER RESPAWN: execve relays PSYS_EXEC and the
//     driver re-spawns the process image on a fresh channel with virtual
//     identity preserved (fds >= FD_BASE, host, pid) — the exec'd image
//     loads its own shim copy, so the filter + handler are re-installed
//     cleanly. fork relays PSYS_FORK onto a pre-created child channel.
//     KNOWN LIMIT: statically-linked binaries never load the shim at all
//     (no LD_PRELOAD), so nothing installs the filter — they run
//     UNSIMULATED. The reference covers them with ptrace
//     (thread_ptrace.c); this plane does not.
//     KNOWN LIMIT: vDSO-backed calls (clock_gettime/gettimeofday/time)
//     never enter the kernel, so seccomp cannot see them. shim_patch_vdso
//     neutralizes this at init by rewriting the vDSO entry points to real
//     `syscall` instructions (written through /proc/self/mem, which
//     bypasses page protections), so they fall into the trapped path. If
//     the patch fails (exotic kernel/vDSO layout) the gap REMAINS for
//     statically-linked binaries whose libc calls the vDSO directly —
//     the failure is logged loudly; dynamically-linked binaries are still
//     covered by libc-symbol interposition either way.
//   * fd space is PARTITIONED: emulated sockets/epolls live at
//     fd >= FD_BASE; anything below is passed through natively. Real-file
//     IO therefore costs zero simulator traffic (the reference instead
//     virtualizes the whole fd table and dups real files into it).
//   * Buffers are memcpy'd through the channel's inline data plane
//     (bounded; large transfers chunk at DATA_MAX per call) rather than
//     read remotely out of plugin memory by the simulator.
//
// Thread model: each thread gets its OWN channel (pthread_create relays
// PSYS_THREAD_NEW; the driver hands back a fresh channel path). The driver
// enforces one-runnable-thread-per-process between syscalls, which is what
// keeps multithreaded apps deterministic (docs/multiproc_design.md).

#include "../common/ipc.h"

#include <arpa/inet.h>
#include <atomic>
#include <dlfcn.h>
#include <elf.h>
#include <errno.h>
#include <fcntl.h>
#include <sys/auxv.h>
#include <linux/audit.h>
#include <linux/filter.h>
#include <linux/seccomp.h>
#include <netdb.h>
#include <signal.h>
#include <sys/prctl.h>
#include <ucontext.h>
#include <netinet/in.h>
#include <poll.h>
#include <sys/un.h>
#include <sched.h>
#include <pthread.h>
#include <stdarg.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <climits>
#include <ifaddrs.h>
#include <net/if.h>
#include <dirent.h>
#include <sys/mman.h>
#include <sys/resource.h>
#include <sys/stat.h>
#include <sys/random.h>
#include <sys/select.h>
#include <sys/socket.h>
#include <sys/syscall.h>
#include <sys/time.h>
#include <sys/timerfd.h>
#include <sys/wait.h>
#include <sys/types.h>
#include <sys/uio.h>
#include <sys/utsname.h>
#include <time.h>
#include <unistd.h>

using namespace shadow_tpu;

// ---------------------------------------------------------------------------
// the syscall gate: the ONE code location the seccomp filter whitelists by
// instruction pointer (reference analog: the shim's designated trampoline
// that the BPF allows, shim.c seccomp install). Every native syscall the
// shim itself makes goes through here, so shim-internal work never traps.
// Raw kernel convention: returns -errno on failure.
// ---------------------------------------------------------------------------

extern "C" __attribute__((noinline, aligned(256), section(".shim_gate")))
long shim_gate_syscall(long n, long a0, long a1, long a2, long a3, long a4,
                       long a5) {
#if defined(__x86_64__)
  long ret;
  register long r10 __asm__("r10") = a3;
  register long r8 __asm__("r8") = a4;
  register long r9 __asm__("r9") = a5;
  __asm__ volatile("syscall"
                   : "=a"(ret)
                   : "0"(n), "D"(a0), "S"(a1), "d"(a2), "r"(r10), "r"(r8),
                     "r"(r9)
                   : "rcx", "r11", "memory");
  return ret;
#else
  long r = ::syscall(n, a0, a1, a2, a3, a4, a5);
  return r < 0 ? -(long)errno : r;
#endif
}

namespace {

// size of the IP window the BPF whitelists around shim_gate_syscall
constexpr uint32_t GATE_WINDOW = 256;

// libc-convention wrapper over the gate: errno + -1 on failure. Variadic
// like syscall(2) so pointer args pass without explicit casts.
template <typename... Args>
long sys_native(long n, Args... args) {
  long vals[] = {(long)(args)..., 0, 0, 0, 0, 0, 0};
  long r = shim_gate_syscall(n, vals[0], vals[1], vals[2], vals[3], vals[4],
                             vals[5]);
  if (r < 0 && r > -4096) {
    errno = (int)-r;
    return -1;
  }
  return r;
}

Channel* g_ch = nullptr;  // process-primary channel (thread 0's)
long g_spin = 8192;
int g_debug = 0;
// count of virtual-signal handler invocations on this thread (reply
// piggyback path) — lets composed mask-swapping waits (ppoll/epoll_pwait)
// report EINTR when a pending signal fires at the mask swap, as the
// kernel's atomic form would
thread_local uint64_t g_sig_handled = 0;
int g_log_stamp = 0;  // ENV_LOG_STAMP: sim-time prefix on stdout/stderr lines
// per-fd (stdout, stderr) at-beginning-of-line state for the stamper
bool g_at_bol[2] = {true, true};
// Never-cleared channel alias for sim-time reads: exit teardown nulls g_ch
// (shim_notify_exit) BEFORE stdio flushes its buffers, and those flushed
// lines still deserve stamps — the shm stays mapped for the process life.
Channel* g_stamp_ch = nullptr;
// Thread-local channel: every pthread_create'd thread gets its OWN shm
// channel from the driver (reference analog: per-thread IPC blocks,
// thread_preload.c:131-179). Threads without one (e.g. raw clone) share
// g_ch under a raw spinlock — NOT a pthread mutex, because pthread mutexes
// are interposed below and their contended path relays through ipc_call.
__thread Channel* t_ch = nullptr;
std::atomic_flag g_ch_lock = ATOMIC_FLAG_INIT;

inline Channel* cur_channel() { return t_ch ? t_ch : g_ch; }

void raw_lock(std::atomic_flag* f) {
  while (f->test_and_set(std::memory_order_acquire)) {
#if defined(__x86_64__)
    __builtin_ia32_pause();
#endif
  }
}
void raw_unlock(std::atomic_flag* f) { f->clear(std::memory_order_release); }

#define SHIM_LOG(...)                                 \
  do {                                                \
    if (g_debug) {                                    \
      fprintf(stderr, "[shadow-tpu-shim %d] ", getpid()); \
      fprintf(stderr, __VA_ARGS__);                   \
      fprintf(stderr, "\n");                          \
    }                                                 \
  } while (0)

bool is_managed_fd(int fd) { return g_ch != nullptr && fd >= FD_BASE; }

// Terminate WITHOUT the driver notification (raw exit_group): used by
// shim-internal teardown paths where the driver already knows (MSG_STOP,
// exec respawn) or where notifying would recurse.
[[noreturn]] void raw_exit(int status) {
  sys_native(SYS_exit_group, status);
  __builtin_unreachable();
}

void shim_install_seccomp();  // defined at the bottom (needs the wrappers)
void shim_patch_vdso();       // defined at the bottom
extern "C" void shim_install_tsc_trap();  // rdtsc virtualization (tsc.c)
void shim_notify_exit(int status, void*);  // defined with the thread plane

// One request/response round trip. data_in/data_in_len ride to the driver;
// the reply's inline data is copied to data_out (up to data_out_cap).
// Returns the driver's ret, with errno set for negative returns.
int64_t ipc_call(int64_t sysno, const int64_t args[6], const void* data_in,
                 uint32_t data_in_len, void* data_out, uint32_t data_out_cap,
                 uint32_t* data_out_len) {
  Channel* ch = cur_channel();
  if (!ch) {
    errno = ENOSYS;
    return -1;
  }
  // Every g_ch user — including the main thread — takes the spinlock:
  // a thread whose own channel failed to map (or a raw-clone thread)
  // falls back to g_ch and would otherwise race the main thread on it.
  const bool shared = (ch == g_ch);
  if (shared) raw_lock(&g_ch_lock);
  ch->type = MSG_SYSCALL;
  ch->sysno = sysno;
  for (int i = 0; i < 6; i++) ch->args[i] = args ? args[i] : 0;
  uint32_t n = data_in_len > IPC_DATA_MAX ? IPC_DATA_MAX : data_in_len;
  ch->data_len = (int32_t)n;
  if (n && data_in) memcpy(ch->data, data_in, n);
  sem_post(&ch->to_driver);
  sem_wait_spinning(&ch->to_shim, g_spin);

  int64_t ret = ch->ret;
  int32_t mtype = ch->type;
  int32_t sig_no = ch->sig_no;
  int32_t sig_flags = ch->sig_flags;
  uint64_t sig_handler = ch->sig_handler;
  uint32_t out_n = 0;
  if (data_out && ch->data_len > 0) {
    out_n = (uint32_t)ch->data_len;
    if (out_n > data_out_cap) out_n = data_out_cap;
    memcpy(data_out, ch->data, out_n);
  }
  if (data_out_len) *data_out_len = out_n;
  if (shared) raw_unlock(&g_ch_lock);

  if (mtype == MSG_STOP) {
    SHIM_LOG("driver requested stop");
    raw_exit((int)ret);
  }
  // Virtual signal piggybacked on the reply (driver-side signal.c analog):
  // invoke the app's registered handler here, at a syscall boundary — the
  // deterministic delivery point. The transaction above is complete, so
  // handler-made syscalls recurse safely through the channel.
  if (sig_no > 0 && sig_handler != 0) {
    SHIM_LOG("delivering virtual signal %d", sig_no);
    g_sig_handled++;  // ppoll/pselect compose: detect delivery-on-entry
    if (sig_flags & 1) {  // SA_SIGINFO-style handler
      siginfo_t si;
      memset(&si, 0, sizeof(si));
      si.si_signo = sig_no;
      ((void (*)(int, siginfo_t*, void*))sig_handler)(sig_no, &si, nullptr);
    } else {
      ((void (*)(int))sig_handler)(sig_no);
    }
    // handler done: restore the pre-delivery mask (driver auto-blocked the
    // signal + sa_mask for the handler's duration — Linux semantics). The
    // return reply may itself carry the NEXT now-unblocked pending signal.
    ipc_call(PSYS_SIG_RETURN, nullptr, nullptr, 0, nullptr, 0, nullptr);
  }
  if (mtype == MSG_DO_NATIVE) {
    return sys_native((long)sysno, args[0], args[1], args[2], args[3],
                      args[4], args[5]);
  }
  if (ret < 0) {
    errno = (int)-ret;
    return -1;
  }
  return ret;
}

int64_t ipc_call6(int64_t sysno, int64_t a0 = 0, int64_t a1 = 0,
                  int64_t a2 = 0, int64_t a3 = 0, int64_t a4 = 0,
                  int64_t a5 = 0) {
  int64_t args[6] = {a0, a1, a2, a3, a4, a5};
  return ipc_call(sysno, args, nullptr, 0, nullptr, 0, nullptr);
}

// Extract (ipv4 host-order, port host-order) from a sockaddr.
bool parse_inet(const struct sockaddr* addr, socklen_t len, uint32_t* ip,
                uint16_t* port) {
  if (!addr || len < (socklen_t)sizeof(struct sockaddr_in)) return false;
  if (addr->sa_family != AF_INET) return false;
  const struct sockaddr_in* sin = (const struct sockaddr_in*)addr;
  *ip = ntohl(sin->sin_addr.s_addr);
  *port = ntohs(sin->sin_port);
  return true;
}

void fill_inet(struct sockaddr* addr, socklen_t* alen, uint32_t ip,
               uint16_t port) {
  if (!addr || !alen) return;
  struct sockaddr_in sin;
  memset(&sin, 0, sizeof(sin));
  sin.sin_family = AF_INET;
  sin.sin_addr.s_addr = htonl(ip);
  sin.sin_port = htons(port);
  socklen_t n = *alen < (socklen_t)sizeof(sin) ? *alen : (socklen_t)sizeof(sin);
  memcpy(addr, &sin, n);
  *alen = (socklen_t)sizeof(sin);
}

__attribute__((constructor)) void shim_init() {
  const char* path = getenv(ENV_SHM);
  if (!path) return;  // not under the simulator; stay inert
  const char* spin = getenv(ENV_SPIN);
  if (spin) g_spin = atol(spin);
  g_debug = getenv(ENV_DEBUG) != nullptr;
  const char* stamp = getenv(ENV_LOG_STAMP);
  g_log_stamp = stamp && strcmp(stamp, "0") != 0;
  int fd = open(path, O_RDWR);
  if (fd < 0) {
    fprintf(stderr, "shadow-tpu-shim: cannot open %s: %s\n", path,
            strerror(errno));
    return;
  }
  void* p = (void*)sys_native(SYS_mmap, (long)nullptr, sizeof(Channel),
                              PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
  close(fd);
  if (p == MAP_FAILED || ((Channel*)p)->magic != IPC_MAGIC) {
    fprintf(stderr, "shadow-tpu-shim: bad channel mapping\n");
    return;
  }
  g_ch = (Channel*)p;
  g_stamp_ch = g_ch;
  t_ch = g_ch;  // the main thread owns the primary channel
  g_ch->shim_pid = getpid();
  SHIM_LOG("attached, channel=%s", path);
  // HELLO round trip: driver replies with the current sim time
  g_ch->type = MSG_HELLO;
  g_ch->ret = getpid();
  g_ch->data_len = 0;
  sem_post(&g_ch->to_driver);
  sem_wait_spinning(&g_ch->to_shim, g_spin);
  // deterministic process-done notification (fork children inherit this
  // registration and notify on their own channel)
  on_exit(shim_notify_exit, nullptr);
  const char* sec = getenv(ENV_SECCOMP);
  if (!sec || strcmp(sec, "0") != 0) {
    shim_patch_vdso();  // before the filter: time must reach the kernel
    shim_install_seccomp();
    shim_install_tsc_trap();  // raw rdtsc reads the virtual clock too
  }
}

}  // namespace

// ---------------------------------------------------------------------------
// sockets
// ---------------------------------------------------------------------------

extern "C" {

int socket(int domain, int type, int protocol) {
  // AF_INET and AF_UNIX are simulated; everything else stays native
  if (!g_ch || (domain != AF_INET && domain != AF_UNIX))
    return (int)sys_native(SYS_socket, domain, type, protocol);
  return (int)ipc_call6(SYS_socket, domain, type, protocol);
}

// Extract a sockaddr_un path ('@' prefix encodes the abstract namespace).
// Returns the path length (0 on failure).
static size_t parse_unix_path(const struct sockaddr* addr, socklen_t len,
                              char* out, size_t cap) {
  if (!addr || addr->sa_family != AF_UNIX) return 0;
  const struct sockaddr_un* sun = (const struct sockaddr_un*)addr;
  size_t off = offsetof(struct sockaddr_un, sun_path);
  if ((size_t)len <= off) return 0;
  size_t plen = (size_t)len - off;
  if (plen > sizeof(sun->sun_path)) plen = sizeof(sun->sun_path);
  size_t n = 0;
  if (sun->sun_path[0] == '\0') {  // abstract namespace
    if (cap < 1) return 0;
    out[n++] = '@';
    for (size_t i = 1; i < plen && n < cap; i++) out[n++] = sun->sun_path[i];
  } else {
    for (size_t i = 0; i < plen && n < cap && sun->sun_path[i]; i++)
      out[n++] = sun->sun_path[i];
  }
  return n;
}

int socketpair(int domain, int type, int protocol, int sv[2]) {
  if (!g_ch || domain != AF_UNIX)
    return (int)sys_native(SYS_socketpair, domain, type, protocol,
                           (long)sv);
  int64_t args[6] = {domain, type, protocol, 0, 0, 0};
  int32_t out[2] = {0, 0};
  uint32_t out_len = 0;
  int64_t r = ipc_call(SYS_socketpair, args, nullptr, 0, out, sizeof(out),
                       &out_len);
  if (r < 0) return -1;
  if (out_len >= 8 && sv) {
    sv[0] = out[0];
    sv[1] = out[1];
  }
  return 0;
}

int bind(int fd, const struct sockaddr* addr, socklen_t len) {
  if (!is_managed_fd(fd)) return (int)sys_native(SYS_bind, fd, addr, len);
  if (addr && addr->sa_family == AF_UNIX) {
    char path[110];
    size_t n = parse_unix_path(addr, len, path, sizeof(path));
    if (!n) {
      errno = EINVAL;
      return -1;
    }
    int64_t args[6] = {fd, 0, 0, 1 /* AF_UNIX path in data */, 0, 0};
    return (int)ipc_call(SYS_bind, args, path, (uint32_t)n, nullptr, 0,
                         nullptr);
  }
  uint32_t ip = 0;
  uint16_t port = 0;
  if (!parse_inet(addr, len, &ip, &port)) {
    errno = EINVAL;
    return -1;
  }
  return (int)ipc_call6(SYS_bind, fd, ip, port);
}

int listen(int fd, int backlog) {
  if (!is_managed_fd(fd)) return (int)sys_native(SYS_listen, fd, backlog);
  return (int)ipc_call6(SYS_listen, fd, backlog);
}

int connect(int fd, const struct sockaddr* addr, socklen_t len) {
  if (!is_managed_fd(fd)) return (int)sys_native(SYS_connect, fd, addr, len);
  if (addr && addr->sa_family == AF_UNIX) {
    char path[110];
    size_t n = parse_unix_path(addr, len, path, sizeof(path));
    if (!n) {
      errno = EINVAL;
      return -1;
    }
    int64_t args[6] = {fd, 0, 0, 1, 0, 0};
    return (int)ipc_call(SYS_connect, args, path, (uint32_t)n, nullptr, 0,
                         nullptr);
  }
  uint32_t ip = 0;
  uint16_t port = 0;
  if (!parse_inet(addr, len, &ip, &port)) {
    errno = EINVAL;
    return -1;
  }
  return (int)ipc_call6(SYS_connect, fd, ip, port);
}

// ---------------------------------------------------------------------------
// virtual signals (reference: syscall/signal.c emulation). The driver owns
// disposition tables, pending queues and per-thread masks; handlers run at
// syscall boundaries via the reply's sig_* fields (see ipc_call). Only the
// classic app-level set is virtualized — SIGSYS stays native (the seccomp
// backstop owns it), as do the fatal fault signals.
// ---------------------------------------------------------------------------

static constexpr uint64_t VIRT_SIG_MASK =
    (1ULL << (SIGHUP - 1)) | (1ULL << (SIGINT - 1)) |
    (1ULL << (SIGQUIT - 1)) | (1ULL << (SIGUSR1 - 1)) |
    (1ULL << (SIGUSR2 - 1)) | (1ULL << (SIGPIPE - 1)) |
    (1ULL << (SIGALRM - 1)) | (1ULL << (SIGTERM - 1)) |
    (1ULL << (SIGCHLD - 1));

static bool is_virt_sig(int sig) {
  return sig >= 1 && sig <= 64 && ((VIRT_SIG_MASK >> (sig - 1)) & 1);
}

// ---------------------------------------------------------------------------
// rdtsc virtualization (reference analog: host/tsc.c:127). PR_SET_TSC
// makes every raw rdtsc/rdtscp in app code fault; the SIGSEGV handler
// decodes the two instruction forms and emulates them from the channel's
// last-stamped sim time (a plain memory read — async-signal-safe): a
// virtual 1 GHz TSC where 1 cycle == 1 sim-ns. App timing loops built on
// rdtsc therefore read DETERMINISTIC virtual time instead of the real
// machine's, like every other clock under the simulator. An app's own
// SIGSEGV handler (registered through our sigaction) chains for
// non-rdtsc faults.
// ---------------------------------------------------------------------------

struct sigaction g_app_segv;   // app's chained SIGSEGV disposition
bool g_app_segv_set = false;
bool g_tsc_trap_on = false;    // emulator installed (gates the intercepts)

void on_sigsegv_tsc(int sig, siginfo_t* info, void* vctx) {
#if defined(__x86_64__)
  ucontext_t* uc = (ucontext_t*)vctx;
  greg_t* g = uc->uc_mcontext.gregs;
  const uint8_t* ip = (const uint8_t*)g[REG_RIP];
  // PR_TSC faults arrive with si_code SI_KERNEL and RIP at the (mapped,
  // executable) rdtsc insn; genuine memory faults are SEGV_MAPERR/ACCERR
  // — gate on that BEFORE reading *ip, or a wild jump to an unmapped
  // address would re-fault inside this handler
  if (info->si_code == SI_KERNEL && ip && ip[0] == 0x0F &&
      (ip[1] == 0x31 || (ip[1] == 0x01 && ip[2] == 0xF9))) {
    Channel* c = cur_channel();
    uint64_t ns = c ? (uint64_t)c->sim_time_ns : 0;
    // The channel stamp only advances at syscalls, so a busy-wait
    // calibrated purely on rdtsc (no syscall in the loop) would read a
    // frozen clock and spin forever. Advance the emulated TSC by one
    // virtual cycle (1 ns) per read past the stamp — deterministic
    // (per-thread counter, one-thread-at-a-time scheduling), monotonic,
    // and a pure-rdtsc delay loop of N cycles now terminates after N
    // reads while staying pinned to sim time whenever syscalls stamp it.
    static thread_local uint64_t last_tsc_read = 0;
    if (ns <= last_tsc_read) ns = last_tsc_read + 1;
    last_tsc_read = ns;
    g[REG_RAX] = (greg_t)(ns & 0xFFFFFFFFu);
    g[REG_RDX] = (greg_t)(ns >> 32);
    if (ip[1] == 0x01) {       // rdtscp: also IA32_TSC_AUX -> ECX
      g[REG_RCX] = 0;
      g[REG_RIP] += 3;
    } else {
      g[REG_RIP] += 2;
    }
    return;
  }
#endif
  // not an rdtsc fault: hand to the app's handler if it has a callable
  // one; otherwise die like SIG_DFL (returning would restart the faulting
  // instruction forever — SIG_IGN on a hardware fault is DFL in Linux).
  // Chaining must preserve the app's registered sigaction SEMANTICS, not
  // just its function pointer: block its sa_mask (plus SIGSEGV itself
  // unless it asked SA_NODEFER) around the call, as the kernel would
  // have. sigprocmask is async-signal-safe; if the handler exits via
  // siglongjmp the mask restore below is skipped, but siglongjmp restores
  // the mask saved by sigsetjmp(.., 1) itself — the same contract the
  // handler relies on under the kernel. SA_ONSTACK delivery (Go/JVM
  // stack-overflow recovery on an altstack) is honored because OUR
  // handler is installed with SA_ONSTACK: the kernel already switched to
  // the app's sigaltstack before we run, so the chained call executes on
  // it too.
  if (g_app_segv_set) {
    sigset_t chain_mask = g_app_segv.sa_mask;
    if (!(g_app_segv.sa_flags & SA_NODEFER)) sigaddset(&chain_mask, SIGSEGV);
    sigset_t prev_mask;
    sigprocmask(SIG_BLOCK, &chain_mask, &prev_mask);
    if (g_app_segv.sa_flags & SA_SIGINFO) {
      g_app_segv.sa_sigaction(sig, info, vctx);
      sigprocmask(SIG_SETMASK, &prev_mask, nullptr);
      return;
    }
    if (g_app_segv.sa_handler != SIG_IGN &&
        g_app_segv.sa_handler != SIG_DFL) {
      g_app_segv.sa_handler(sig);
      sigprocmask(SIG_SETMASK, &prev_mask, nullptr);
      return;
    }
    sigprocmask(SIG_SETMASK, &prev_mask, nullptr);
  }
  signal(SIGSEGV, SIG_DFL);
  raise(SIGSEGV);
}

void shim_install_tsc_trap() {
#if defined(__x86_64__)
  struct sigaction sa;
  memset(&sa, 0, sizeof(sa));
  sa.sa_sigaction = on_sigsegv_tsc;
  // SA_ONSTACK: if the app registers a sigaltstack (Go, JVM, Rust guard
  // pages recover stack overflow there), genuine faults must be DELIVERED
  // on it — our handler sits in front of theirs, so it must carry the
  // flag or the chained handler would run on the overflowed stack and
  // double-fault. rdtsc emulation is a few words of stack either way.
  sa.sa_flags = SA_SIGINFO | SA_NODEFER | SA_ONSTACK;
  static auto real_sigaction =
      (int (*)(int, const struct sigaction*, struct sigaction*))dlsym(
          RTLD_NEXT, "sigaction");
  real_sigaction(SIGSEGV, &sa, nullptr);
  if (prctl(PR_SET_TSC, PR_TSC_SIGSEGV, 0, 0, 0) != 0) {
    SHIM_LOG("PR_SET_TSC unavailable: raw rdtsc stays unvirtualized");
  } else {
    g_tsc_trap_on = true;
  }
#endif
}

int sigaction(int sig, const struct sigaction* act, struct sigaction* old) {
  static auto real_sigaction =
      (int (*)(int, const struct sigaction*, struct sigaction*))dlsym(
          RTLD_NEXT, "sigaction");
  if (g_ch && sig == SIGSEGV && g_tsc_trap_on) {
    // keep the rdtsc trap installed; the app's handler chains for
    // genuine faults (on_sigsegv_tsc dispatches non-rdtsc hits to it).
    // Only when the trap is actually installed — otherwise the app's
    // registration must reach the kernel normally.
    if (old) *old = g_app_segv;
    if (act) {
      g_app_segv = *act;
      g_app_segv_set = true;
    }
    return 0;
  }
  if (!g_ch || !is_virt_sig(sig)) return real_sigaction(sig, act, old);
  int64_t handler = 0, flags = 0;
  uint64_t mask = 0;
  if (act) {
    handler = (act->sa_flags & SA_SIGINFO) ? (int64_t)act->sa_sigaction
                                           : (int64_t)act->sa_handler;
    flags = act->sa_flags;
    memcpy(&mask, &act->sa_mask, sizeof(mask));
  }
  int64_t args[6] = {sig, handler, flags, (int64_t)mask, act ? 1 : 0, 0};
  uint8_t out[16];
  uint32_t out_len = 0;
  int64_t r = ipc_call(SYS_rt_sigaction, args, nullptr, 0, out, sizeof(out),
                       &out_len);
  if (r < 0) return -1;
  if (old && out_len >= 12) {
    memset(old, 0, sizeof(*old));
    uint64_t oh;
    uint32_t of;
    memcpy(&oh, out, 8);
    memcpy(&of, out + 8, 4);
    old->sa_flags = (int)of;
    if (of & SA_SIGINFO)
      old->sa_sigaction = (void (*)(int, siginfo_t*, void*))oh;
    else
      old->sa_handler = (void (*)(int))oh;
  }
  return 0;
}

sighandler_t signal(int sig, sighandler_t h) {
  static auto real_signal =
      (sighandler_t(*)(int, sighandler_t))dlsym(RTLD_NEXT, "signal");
  if (!g_ch || !is_virt_sig(sig)) return real_signal(sig, h);
  struct sigaction act, old;
  memset(&act, 0, sizeof(act));
  act.sa_handler = h;
  act.sa_flags = SA_RESTART;
  if (sigaction(sig, &act, &old) != 0) return SIG_ERR;
  return old.sa_handler;
}

int sigprocmask(int how, const sigset_t* set, sigset_t* old) {
  static auto real_sigprocmask =
      (int (*)(int, const sigset_t*, sigset_t*))dlsym(RTLD_NEXT,
                                                      "sigprocmask");
  if (!g_ch) return real_sigprocmask(how, set, old);
  // native first, with the virtualized signals removed (they are never
  // delivered natively; the driver owns their mask)
  sigset_t nset;
  sigset_t nold;
  sigemptyset(&nold);
  if (set) {
    nset = *set;
    for (int s = 1; s <= 64; s++)
      if (is_virt_sig(s)) sigdelset(&nset, s);
  }
  if (real_sigprocmask(how, set ? &nset : nullptr, &nold) != 0) return -1;
  uint64_t vm = 0;
  if (set) {
    memcpy(&vm, set, sizeof(vm));
    vm &= VIRT_SIG_MASK;
  }
  // how: 0 block / 1 unblock / 2 setmask / 3 query-only
  int64_t vhow = set ? (int64_t)how : 3;
  int64_t args[6] = {vhow, (int64_t)vm, 0, 0, 0, 0};
  uint8_t out[8];
  uint32_t out_len = 0;
  int64_t r =
      ipc_call(SYS_rt_sigprocmask, args, nullptr, 0, out, sizeof(out),
               &out_len);
  if (old) {
    uint64_t om = 0;
    memcpy(&om, &nold, sizeof(om));
    om &= ~VIRT_SIG_MASK;
    uint64_t vold = 0;
    if (r >= 0 && out_len >= 8) memcpy(&vold, out, 8);
    om |= (vold & VIRT_SIG_MASK);
    memset(old, 0, sizeof(*old));
    memcpy(old, &om, sizeof(om));
  }
  return 0;
}

int pthread_sigmask(int how, const sigset_t* set, sigset_t* old) {
  if (!g_ch) {
    static auto real = (int (*)(int, const sigset_t*, sigset_t*))dlsym(
        RTLD_NEXT, "pthread_sigmask");
    return real(how, set, old);
  }
  return sigprocmask(how, set, old) == 0 ? 0 : errno;
}

int kill(pid_t pid, int sig) {
  if (!g_ch || (sig != 0 && !is_virt_sig(sig)))
    return (int)sys_native(SYS_kill, pid, sig);
  // Group/broadcast kills MUST stay virtual: the managed process shares
  // the driver's real process group, so a native kill(0)/kill(-1) would
  // signal the simulator itself. Wire encoding: arg2=1 marks a group kill
  // (pid 0 = caller's lineage, -1 = all managed, -g = group of leader g).
  if (pid <= 0)
    return (int)ipc_call6(SYS_kill, pid == -1 ? -1 : -pid, sig, 1);
  return (int)ipc_call6(SYS_kill, pid == getpid() ? 0 : pid, sig);
}

int raise(int sig) {
  if (!g_ch || !is_virt_sig(sig)) {
    static auto real = (int (*)(int))dlsym(RTLD_NEXT, "raise");
    return real(sig);
  }
  return (int)ipc_call6(SYS_kill, 0, sig);
}

int accept4(int fd, struct sockaddr* addr, socklen_t* alen, int flags) {
  if (!is_managed_fd(fd))
    return (int)sys_native(SYS_accept4, fd, addr, alen, flags);
  // reply data = [u32 peer_ip, u16 peer_port] packed in ret-adjacent words
  int64_t args[6] = {fd, flags, 0, 0, 0, 0};
  uint8_t out[8];
  uint32_t out_len = 0;
  int64_t r = ipc_call(SYS_accept4, args, nullptr, 0, out, sizeof(out),
                       &out_len);
  if (r >= 0 && out_len >= 6 && addr && alen) {
    uint32_t ip;
    uint16_t port;
    memcpy(&ip, out, 4);
    memcpy(&port, out + 4, 2);
    fill_inet(addr, alen, ip, port);
  }
  return (int)r;
}

int accept(int fd, struct sockaddr* addr, socklen_t* alen) {
  return accept4(fd, addr, alen, 0);
}

ssize_t sendto(int fd, const void* buf, size_t n, int flags,
               const struct sockaddr* addr, socklen_t alen) {
  if (!is_managed_fd(fd))
    return sys_native(SYS_sendto, fd, buf, n, flags, addr, alen);
  uint32_t ip = 0;
  uint16_t port = 0;
  int has_addr = parse_inet(addr, alen, &ip, &port) ? 1 : 0;
  if (n > IPC_DATA_MAX) n = IPC_DATA_MAX;  // caller loops for the rest
  int64_t args[6] = {fd, (int64_t)n, flags, has_addr, ip, port};
  return (ssize_t)ipc_call(SYS_sendto, args, buf, (uint32_t)n, nullptr, 0,
                           nullptr);
}

ssize_t send(int fd, const void* buf, size_t n, int flags) {
  if (!is_managed_fd(fd)) return sys_native(SYS_sendto, fd, buf, n, flags, 0, 0);
  return sendto(fd, buf, n, flags, nullptr, 0);
}

ssize_t recvfrom(int fd, void* buf, size_t n, int flags,
                 struct sockaddr* addr, socklen_t* alen) {
  if (!is_managed_fd(fd))
    return sys_native(SYS_recvfrom, fd, buf, n, flags, addr, alen);
  size_t want = n > IPC_DATA_MAX ? IPC_DATA_MAX : n;
  int64_t args[6] = {fd, (int64_t)want, flags, addr ? 1 : 0, 0, 0};
  // reply: data = [u32 src_ip, u16 src_port, payload...]
  static thread_local uint8_t tmp[IPC_DATA_MAX];
  uint32_t out_len = 0;
  int64_t r =
      ipc_call(SYS_recvfrom, args, nullptr, 0, tmp, IPC_DATA_MAX, &out_len);
  if (r < 0) return -1;
  uint32_t hdr = 6;
  uint32_t payload = out_len > hdr ? out_len - hdr : 0;
  if (payload > want) payload = (uint32_t)want;
  if (payload && buf) memcpy(buf, tmp + hdr, payload);
  if (addr && alen && out_len >= hdr) {
    uint32_t ip;
    uint16_t port;
    memcpy(&ip, tmp, 4);
    memcpy(&port, tmp + 4, 2);
    fill_inet(addr, alen, ip, port);
  }
  return (ssize_t)r;
}

ssize_t recv(int fd, void* buf, size_t n, int flags) {
  if (!is_managed_fd(fd)) return sys_native(SYS_recvfrom, fd, buf, n, flags, 0, 0);
  return recvfrom(fd, buf, n, flags, nullptr, nullptr);
}

ssize_t read(int fd, void* buf, size_t n) {
  if (!is_managed_fd(fd)) return sys_native(SYS_read, fd, buf, n);
  // generic read (sockets, pipes, eventfds, timerfds); reply data = payload
  size_t want = n > IPC_DATA_MAX ? IPC_DATA_MAX : n;
  int64_t args[6] = {fd, (int64_t)want, 0, 0, 0, 0};
  uint32_t out_len = 0;
  int64_t r = ipc_call(SYS_read, args, nullptr, 0, buf, (uint32_t)want,
                       &out_len);
  return (ssize_t)r;
}

// Sim-time line stamping for stdout/stderr (reference analog:
// shim_logger.c — managed-process log lines carry the SIMULATED clock, not
// wall time). The stamp is the channel's last-reply sim_time_ns: every
// syscall reply refreshes it, so a line printed between syscalls carries
// the time of the preceding syscall boundary — the same resolution the
// reference gets from its start-offset + emulated clock. Prefix format
// matches the driver's log lines (utils/log.py _fmt_time).
ssize_t stamped_write(int fd, const uint8_t* buf, size_t n) {
  Channel* c = cur_channel();
  if (!c) c = g_stamp_ch;
  int64_t ns = c ? c->sim_time_ns : 0;
  char pfx[40];
  int64_t us = ns / 1000;
  int64_t s = us / 1000000;
  int plen = snprintf(pfx, sizeof(pfx),
                      "%02lld:%02lld:%02lld.%06lld [stdio] ",
                      (long long)(s / 3600), (long long)(s / 60 % 60),
                      (long long)(s % 60), (long long)(us % 1000000));
  bool* bol = &g_at_bol[fd == 2 ? 1 : 0];
  // full-write helper: stdio treats a successful flush as all-or-nothing,
  // so retry short counts (pipe backpressure) until done or hard error
  auto write_all = [fd](const void* p, size_t len) -> bool {
    size_t off = 0;
    while (off < len) {
      ssize_t w = sys_native(SYS_write, fd, (const uint8_t*)p + off,
                             len - off);
      if (w < 0) {
        if (errno == EINTR) continue;
        return false;
      }
      off += (size_t)w;
    }
    return true;
  };
  size_t i = 0;
  while (i < n) {
    size_t j = i;
    while (j < n && buf[j] != '\n') j++;
    bool nl = j < n;
    if (*bol && (j > i || nl))
      if (!write_all(pfx, (size_t)plen)) return (i == 0) ? -1 : (ssize_t)i;
    size_t seg = (nl ? j + 1 : j) - i;
    if (seg && !write_all(buf + i, seg)) return (i == 0) ? -1 : (ssize_t)i;
    *bol = nl;
    i += seg ? seg : 1;
  }
  return (ssize_t)n;
}

ssize_t write(int fd, const void* buf, size_t n) {
  if (!is_managed_fd(fd)) {
    if (g_log_stamp && g_stamp_ch && (fd == 1 || fd == 2) && n)
      return stamped_write(fd, (const uint8_t*)buf, n);
    return sys_native(SYS_write, fd, buf, n);
  }
  if (n > IPC_DATA_MAX) n = IPC_DATA_MAX;  // caller loops for the rest
  int64_t args[6] = {fd, (int64_t)n, 0, 0, 0, 0};
  return (ssize_t)ipc_call(SYS_write, args, buf, (uint32_t)n, nullptr, 0,
                           nullptr);
}

ssize_t readv(int fd, const struct iovec* iov, int iovcnt) {
  if (!is_managed_fd(fd)) return sys_native(SYS_readv, fd, iov, iovcnt);
  // gather into one bounded read, then scatter across the iovecs
  static thread_local uint8_t tmp[IPC_DATA_MAX];
  size_t want = 0;
  for (int i = 0; i < iovcnt; i++) want += iov[i].iov_len;
  if (want > IPC_DATA_MAX) want = IPC_DATA_MAX;
  ssize_t r = read(fd, tmp, want);
  if (r <= 0) return r;
  size_t off = 0;
  for (int i = 0; i < iovcnt && off < (size_t)r; i++) {
    size_t take = iov[i].iov_len;
    if (take > (size_t)r - off) take = (size_t)r - off;
    memcpy(iov[i].iov_base, tmp + off, take);
    off += take;
  }
  return r;
}

ssize_t writev(int fd, const struct iovec* iov, int iovcnt) {
  if (!is_managed_fd(fd)) {
    if (g_log_stamp && g_stamp_ch && (fd == 1 || fd == 2)) {
      ssize_t total = 0;
      for (int i = 0; i < iovcnt; i++) {
        if (!iov[i].iov_len) continue;
        ssize_t w =
            stamped_write(fd, (const uint8_t*)iov[i].iov_base, iov[i].iov_len);
        if (w < 0) return total ? total : w;
        total += w;
        if ((size_t)w < iov[i].iov_len) break;
      }
      return total;
    }
    return sys_native(SYS_writev, fd, iov, iovcnt);
  }
  static thread_local uint8_t tmp[IPC_DATA_MAX];
  size_t n = 0;
  for (int i = 0; i < iovcnt; i++) {
    size_t take = iov[i].iov_len;
    if (take > IPC_DATA_MAX - n) take = IPC_DATA_MAX - n;
    memcpy(tmp + n, iov[i].iov_base, take);
    n += take;
    if (n == IPC_DATA_MAX) break;
  }
  return write(fd, tmp, n);
}

ssize_t sendmsg(int fd, const struct msghdr* msg, int flags) {
  if (!is_managed_fd(fd)) return sys_native(SYS_sendmsg, fd, msg, flags);
  static thread_local uint8_t tmp[IPC_DATA_MAX];
  size_t n = 0;
  for (size_t i = 0; i < msg->msg_iovlen; i++) {
    size_t take = msg->msg_iov[i].iov_len;
    if (take > IPC_DATA_MAX - n) take = IPC_DATA_MAX - n;
    memcpy(tmp + n, msg->msg_iov[i].iov_base, take);
    n += take;
    if (n == IPC_DATA_MAX) break;
  }
  return sendto(fd, tmp, n, flags, (const struct sockaddr*)msg->msg_name,
                (socklen_t)msg->msg_namelen);
}

ssize_t recvmsg(int fd, struct msghdr* msg, int flags) {
  if (!is_managed_fd(fd)) return sys_native(SYS_recvmsg, fd, msg, flags);
  static thread_local uint8_t tmp[IPC_DATA_MAX];
  size_t want = 0;
  for (size_t i = 0; i < msg->msg_iovlen; i++) want += msg->msg_iov[i].iov_len;
  if (want > IPC_DATA_MAX) want = IPC_DATA_MAX;
  socklen_t alen = (socklen_t)msg->msg_namelen;
  ssize_t r = recvfrom(fd, tmp, want, flags,
                       (struct sockaddr*)msg->msg_name,
                       msg->msg_name ? &alen : nullptr);
  if (r <= 0) return r;
  if (msg->msg_name) msg->msg_namelen = alen;
  size_t off = 0;
  for (size_t i = 0; i < msg->msg_iovlen && off < (size_t)r; i++) {
    size_t take = msg->msg_iov[i].iov_len;
    if (take > (size_t)r - off) take = (size_t)r - off;
    memcpy(msg->msg_iov[i].iov_base, tmp + off, take);
    off += take;
  }
  msg->msg_flags = 0;
  if (msg->msg_control) msg->msg_controllen = 0;
  return r;
}

int close(int fd) {
  if (!is_managed_fd(fd)) return (int)sys_native(SYS_close, fd);
  return (int)ipc_call6(SYS_close, fd);
}

int dup(int fd) {
  if (!is_managed_fd(fd)) return (int)sys_native(SYS_dup, fd);
  return (int)ipc_call6(SYS_dup, fd);
}

int dup2(int oldfd, int newfd) {
  if (!is_managed_fd(oldfd)) return (int)sys_native(SYS_dup2, oldfd, newfd);
  return (int)ipc_call6(SYS_dup2, oldfd, newfd);
}

int dup3(int oldfd, int newfd, int flags) {
  if (!is_managed_fd(oldfd)) return (int)sys_native(SYS_dup3, oldfd, newfd, flags);
  return (int)ipc_call6(SYS_dup3, oldfd, newfd, flags);
}

int pipe2(int fds[2], int flags) {
  if (!g_ch) return (int)sys_native(SYS_pipe2, fds, flags);
  // reply data = [i32 read_fd, i32 write_fd]
  int64_t args[6] = {flags, 0, 0, 0, 0, 0};
  uint8_t out[8];
  uint32_t out_len = 0;
  int64_t r = ipc_call(SYS_pipe2, args, nullptr, 0, out, sizeof(out), &out_len);
  if (r < 0) return -1;
  if (out_len >= 8) {
    memcpy(&fds[0], out, 4);
    memcpy(&fds[1], out + 4, 4);
  }
  return 0;
}

int pipe(int fds[2]) { return pipe2(fds, 0); }

int eventfd(unsigned int initval, int flags) {
  if (!g_ch) return (int)sys_native(SYS_eventfd2, initval, flags);
  return (int)ipc_call6(SYS_eventfd2, initval, flags);
}

int timerfd_create(int clockid, int flags) {
  if (!g_ch) return (int)sys_native(SYS_timerfd_create, clockid, flags);
  return (int)ipc_call6(SYS_timerfd_create, clockid, flags);
}

static int64_t ts_to_ns(const struct timespec* ts) {
  return (int64_t)ts->tv_sec * 1000000000LL + ts->tv_nsec;
}

static void ns_to_ts(int64_t ns, struct timespec* ts) {
  ts->tv_sec = ns / 1000000000LL;
  ts->tv_nsec = ns % 1000000000LL;
}

int timerfd_settime(int fd, int flags, const struct itimerspec* new_value,
                    struct itimerspec* old_value) {
  if (!is_managed_fd(fd))
    return (int)sys_native(SYS_timerfd_settime, fd, flags, new_value, old_value);
  // request data = [i64 value_ns, i64 interval_ns]; reply data = old pair
  uint8_t in[16], out[16];
  int64_t v = ts_to_ns(&new_value->it_value);
  int64_t iv = ts_to_ns(&new_value->it_interval);
  memcpy(in, &v, 8);
  memcpy(in + 8, &iv, 8);
  int64_t args[6] = {fd, flags, 0, 0, 0, 0};
  uint32_t out_len = 0;
  int64_t r = ipc_call(SYS_timerfd_settime, args, in, sizeof(in), out,
                       sizeof(out), &out_len);
  if (r < 0) return -1;
  if (old_value && out_len >= 16) {
    int64_t ov, oiv;
    memcpy(&ov, out, 8);
    memcpy(&oiv, out + 8, 8);
    ns_to_ts(ov, &old_value->it_value);
    ns_to_ts(oiv, &old_value->it_interval);
  }
  return 0;
}

int timerfd_gettime(int fd, struct itimerspec* curr) {
  if (!is_managed_fd(fd))
    return (int)sys_native(SYS_timerfd_gettime, fd, curr);
  uint8_t out[16];
  uint32_t out_len = 0;
  int64_t args[6] = {fd, 0, 0, 0, 0, 0};
  int64_t r = ipc_call(SYS_timerfd_gettime, args, nullptr, 0, out, sizeof(out),
                       &out_len);
  if (r < 0) return -1;
  if (curr && out_len >= 16) {
    int64_t v, iv;
    memcpy(&v, out, 8);
    memcpy(&iv, out + 8, 8);
    ns_to_ts(v, &curr->it_value);
    ns_to_ts(iv, &curr->it_interval);
  }
  return 0;
}

int signalfd(int fd, const sigset_t* mask, int flags) {
  // Virtual-signal-plane signalfd (syscall/signal.c surface): reads
  // consume the process's PENDING virtual signals matching the mask —
  // the block-then-read contract apps use with epoll event loops.
  if (!g_ch) return (int)sys_native(SYS_signalfd4, fd, mask, 8, flags);
  uint64_t m = 0;
  if (mask) memcpy(&m, mask, sizeof(m));
  if (m & ~VIRT_SIG_MASK) {
    // A non-virtualized signal (SIGWINCH, realtime, ...) never enters the
    // driver's pending queue, so an fd watching it would silently never
    // fire while the signal stays blocked natively — fail FAST instead.
    SHIM_LOG("signalfd: mask 0x%llx includes non-virtualized signals "
             "(virtual set 0x%llx) — refusing",
             (unsigned long long)m, (unsigned long long)VIRT_SIG_MASK);
    errno = EINVAL;
    return -1;
  }
  int64_t args[6] = {fd, flags, 0, 0, 0, 0};
  return (int)ipc_call(SYS_signalfd4, args, (const uint8_t*)&m, 8, nullptr,
                       0, nullptr);
}

// Shared sigmask-swap guard for the composed mask-swapping waits
// (ppoll/epoll_pwait). The kernel's atomicity guarantee holds in this
// plane because signals only deliver at syscall boundaries: a pending
// signal unblocked by the swap rides the sigprocmask REPLY (its handler
// runs before the wait is entered), which the guard reports as the
// kernel's delivery-on-entry EINTR; one arriving during the wait EINTRs
// the wait itself under the temporary mask.
static int sigmask_swap_enter(const sigset_t* sigmask, sigset_t* oldm) {
  if (!sigmask) return 0;
  // NATIVE pending signals the swap would unblock deliver inside
  // real_sigprocmask without touching g_sig_handled — probe them first
  // (sigpending reports the native plane only; virtual pending rides the
  // driver reply and bumps the counter).
  bool native_hit = false;
  sigset_t pend;
  if (sigpending(&pend) == 0) {
    for (int s = 1; s <= 64; s++)
      if (sigismember(&pend, s) && !sigismember(sigmask, s)) {
        native_hit = true;
        break;
      }
  }
  uint64_t h0 = g_sig_handled;
  sigprocmask(SIG_SETMASK, sigmask, oldm);
  if (g_sig_handled != h0 || native_hit) {
    sigprocmask(SIG_SETMASK, oldm, nullptr);
    errno = EINTR;
    return -1;
  }
  return 0;
}

static void sigmask_swap_exit(const sigset_t* sigmask,
                              const sigset_t* oldm) {
  if (!sigmask) return;
  int saved = errno;
  sigprocmask(SIG_SETMASK, oldm, nullptr);
  errno = saved;
}

int ppoll(struct pollfd* fds, nfds_t nfds, const struct timespec* ts,
          const sigset_t* sigmask) {
  if (!g_ch) {
    static auto real = (int (*)(struct pollfd*, nfds_t,
                                const struct timespec*,
                                const sigset_t*))dlsym(RTLD_NEXT, "ppoll");
    return real(fds, nfds, ts, sigmask);
  }
  if (ts && (ts->tv_sec < 0 || ts->tv_nsec < 0 ||
             ts->tv_nsec >= 1000000000L)) {
    errno = EINVAL;  // kernel contract for an invalid timespec
    return -1;
  }
  sigset_t oldm;
  if (sigmask_swap_enter(sigmask, &oldm) != 0) return -1;
  int timeout_ms = -1;
  if (ts) {
    int64_t ms = (int64_t)ts->tv_sec * 1000 + (ts->tv_nsec + 999999) / 1000000;
    timeout_ms = ms > INT_MAX ? INT_MAX : (int)ms;  // clamp, don't wrap
  }
  int r = poll(fds, nfds, timeout_ms);
  sigmask_swap_exit(sigmask, &oldm);
  return r;
}

int epoll_pwait(int epfd, struct epoll_event* evs, int maxevents,
                int timeout_ms, const sigset_t* sigmask) {
  if (!g_ch) {
    static auto real = (int (*)(int, struct epoll_event*, int, int,
                                const sigset_t*))dlsym(RTLD_NEXT,
                                                       "epoll_pwait");
    return real(epfd, evs, maxevents, timeout_ms, sigmask);
  }
  sigset_t oldm;
  if (sigmask_swap_enter(sigmask, &oldm) != 0) return -1;
  int r = epoll_wait(epfd, evs, maxevents, timeout_ms);
  sigmask_swap_exit(sigmask, &oldm);
  return r;
}

int pselect(int nfds, fd_set* rd, fd_set* wr, fd_set* ex,
            const struct timespec* ts, const sigset_t* sigmask) {
  if (!g_ch) {
    static auto real =
        (int (*)(int, fd_set*, fd_set*, fd_set*, const struct timespec*,
                 const sigset_t*))dlsym(RTLD_NEXT, "pselect");
    return real(nfds, rd, wr, ex, ts, sigmask);
  }
  if (ts && (ts->tv_sec < 0 || ts->tv_nsec < 0 ||
             ts->tv_nsec >= 1000000000L)) {
    errno = EINVAL;
    return -1;
  }
  sigset_t oldm;
  if (sigmask_swap_enter(sigmask, &oldm) != 0) return -1;
  struct timeval tv, *tvp = nullptr;
  if (ts) {
    tv.tv_sec = ts->tv_sec;
    tv.tv_usec = (ts->tv_nsec + 999) / 1000;
    if (tv.tv_usec >= 1000000) {  // round-up overflow: carry, or the
      tv.tv_sec += 1;             // kernel rejects the timeval (EINVAL)
      tv.tv_usec -= 1000000;
    }
    tvp = &tv;
  }
  int r = select(nfds, rd, wr, ex, tvp);
  sigmask_swap_exit(sigmask, &oldm);
  return r;
}

// ---------------------------------------------------------------------------
// Deterministic resource limits + usage (rlimit.c-class surface): limits
// are app-visible state, so reading the real machine's would leak
// nondeterminism across hosts; the table below is fixed per process (fork
// children inherit the current values with the copied address space).
// getrusage serves the VIRTUAL clock as CPU time.
// ---------------------------------------------------------------------------

static struct rlimit g_rlim[16];
static bool g_rlim_init = false;
static pthread_mutex_t g_rlim_mu = PTHREAD_MUTEX_INITIALIZER;

static void rlim_init_locked() {
  if (g_rlim_init) return;
  for (int i = 0; i < 16; i++) {
    g_rlim[i].rlim_cur = RLIM_INFINITY;
    g_rlim[i].rlim_max = RLIM_INFINITY;
  }
  // Soft limit must clear FD_BASE (1000) + the whole managed-fd budget:
  // the driver allocates virtual fds upward from FD_BASE, and a
  // synthesized 1024 would tell apps (and their fd-hygiene sweeps) that
  // descriptors the driver legitimately hands out cannot exist. The
  // driver clamps alloc_fd to this same value (procs/driver.VIRT_NOFILE).
  g_rlim[RLIMIT_NOFILE].rlim_cur = 65536;
  g_rlim[RLIMIT_NOFILE].rlim_max = 262144;
  g_rlim[RLIMIT_STACK].rlim_cur = 8ull << 20;
  g_rlim_init = true;
}

int getrlimit(int res, struct rlimit* rl) {
  if (!g_ch) return (int)sys_native(SYS_getrlimit, res, rl);
  if (res < 0 || res >= 16 || !rl) {
    errno = EINVAL;
    return -1;
  }
  pthread_mutex_lock(&g_rlim_mu);
  rlim_init_locked();
  *rl = g_rlim[res];
  pthread_mutex_unlock(&g_rlim_mu);
  return 0;
}

int setrlimit(int res, const struct rlimit* rl) {
  if (!g_ch) return (int)sys_native(SYS_setrlimit, res, rl);
  if (res < 0 || res >= 16 || !rl || rl->rlim_cur > rl->rlim_max) {
    errno = EINVAL;
    return -1;
  }
  pthread_mutex_lock(&g_rlim_mu);
  rlim_init_locked();
  if (rl->rlim_max > g_rlim[res].rlim_max) {
    pthread_mutex_unlock(&g_rlim_mu);
    errno = EPERM;  // raising the hard limit needs privilege — refuse
    return -1;
  }
  g_rlim[res] = *rl;
  pthread_mutex_unlock(&g_rlim_mu);
  return 0;
}

int prlimit(pid_t pid, __rlimit_resource res, const struct rlimit* nl,
            struct rlimit* ol) {
  if (!g_ch) return (int)sys_native(SYS_prlimit64, pid, res, nl, ol);
  if (pid != 0 && pid != getpid()) {
    errno = EPERM;  // cross-process limits stay out of the sim plane
    return -1;
  }
  if (ol && getrlimit(res, ol) != 0) return -1;
  if (nl) return setrlimit(res, nl);
  return 0;
}

int prlimit64(pid_t pid, __rlimit_resource res, const struct rlimit64* nl,
              struct rlimit64* ol) {
  // x86_64: rlimit == rlimit64 (both 64-bit fields)
  return prlimit(pid, res, (const struct rlimit*)nl, (struct rlimit*)ol);
}

int getrusage(int who, struct rusage* ru) {
  if (!g_ch) return (int)sys_native(SYS_getrusage, who, ru);
  if (!ru) {
    errno = EFAULT;
    return -1;
  }
  // Deterministic synthesis: CPU time = the virtual clock (the CPU model
  // charges simulated processing to it), everything else fixed. Only
  // RUSAGE_SELF carries the clock: children's accumulated time (and
  // per-thread time) report zero — the Linux baseline for a process that
  // has reaped nothing.
  memset(ru, 0, sizeof(*ru));
  if (who == RUSAGE_SELF) {
    Channel* c = cur_channel();
    uint64_t ns = c ? (uint64_t)c->sim_time_ns : 0;
    ru->ru_utime.tv_sec = (time_t)(ns / 1000000000ull);
    ru->ru_utime.tv_usec = (suseconds_t)((ns % 1000000000ull) / 1000);
  }
  ru->ru_maxrss = 65536;  // fixed 64 MiB in KB — deterministic
  return 0;
}

// Virtualized CPU visibility: the driver reports the simulated host's
// CPU count (default 1 — matching the one-runnable-thread determinism
// model), so glibc's __get_nprocs / sysconf(_SC_NPROCESSORS_ONLN) and
// app thread-pool sizing are deterministic instead of leaking the real
// machine's core count. (The reference pins workers but lets nproc
// leak; Tor sizes its threadpool from it — determinism wants this.)
// Returns the RAW KERNEL convention (size of the kernel cpumask copy,
// or -errno) — the SIGSYS dispatcher forwards it as-is; the libc-facing
// wrapper below converts to glibc's 0-on-success.
long sched_getaffinity_raw(pid_t pid, size_t cpusetsize, cpu_set_t* mask) {
  int64_t args[6] = {pid, (int64_t)cpusetsize, 0, 0, 0, 0};
  uint32_t out_len = 0;
  uint8_t tmp[128];
  int64_t r = ipc_call(SYS_sched_getaffinity, args, nullptr, 0, tmp,
                       sizeof(tmp), &out_len);
  if (r < 0) return -(long)errno;
  if (mask && cpusetsize) {
    memset(mask, 0, cpusetsize);
    size_t n = out_len < cpusetsize ? out_len : cpusetsize;
    memcpy(mask, tmp, n);
  }
  return (long)r;
}

int sched_getaffinity(pid_t pid, size_t cpusetsize, cpu_set_t* mask) {
  if (!g_ch)
    return (int)sys_native(SYS_sched_getaffinity, pid, cpusetsize, mask) < 0
               ? -1
               : 0;
  long r = sched_getaffinity_raw(pid, cpusetsize, mask);
  if (r < 0) {
    errno = (int)-r;
    return -1;
  }
  return 0;  // glibc convention
}

long sysconf(int name) {
  static auto real_sysconf = (long (*)(int))dlsym(RTLD_NEXT, "sysconf");
  // glibc's __get_nprocs reads /sys (the REAL machine) on modern
  // versions, so the processor-count queries are answered from the
  // virtualized affinity mask instead.
  if (g_ch && (name == _SC_NPROCESSORS_ONLN || name == _SC_NPROCESSORS_CONF)) {
    cpu_set_t s;
    CPU_ZERO(&s);
    if (sched_getaffinity(0, sizeof(s), &s) == 0) {
      int n = CPU_COUNT(&s);
      if (n > 0) return n;
    }
    return 1;
  }
  return real_sysconf(name);
}

ssize_t getrandom(void* buf, size_t buflen, unsigned int flags) {
  if (!g_ch) return sys_native(SYS_getrandom, buf, buflen, flags);
  // deterministic per-host stream from the simulator's seeded RNG tree
  size_t want = buflen > IPC_DATA_MAX ? IPC_DATA_MAX : buflen;
  int64_t args[6] = {(int64_t)want, flags, 0, 0, 0, 0};
  uint32_t out_len = 0;
  int64_t r = ipc_call(SYS_getrandom, args, nullptr, 0, buf, (uint32_t)want,
                       &out_len);
  return (ssize_t)r;
}

int shutdown(int fd, int how) {
  if (!is_managed_fd(fd)) return (int)sys_native(SYS_shutdown, fd, how);
  return (int)ipc_call6(SYS_shutdown, fd, how);
}

int setsockopt(int fd, int level, int optname, const void* optval,
               socklen_t optlen) {
  if (!is_managed_fd(fd))
    return (int)sys_native(SYS_setsockopt, fd, level, optname, optval, optlen);
  int64_t v = 0;
  if (optval && optlen >= sizeof(int)) v = *(const int*)optval;
  return (int)ipc_call6(SYS_setsockopt, fd, level, optname, v);
}

int getsockopt(int fd, int level, int optname, void* optval,
               socklen_t* optlen) {
  if (!is_managed_fd(fd))
    return (int)sys_native(SYS_getsockopt, fd, level, optname, optval, optlen);
  int64_t r = ipc_call6(SYS_getsockopt, fd, level, optname);
  if (r < 0) return -1;
  if (optval && optlen && *optlen >= sizeof(int)) {
    *(int*)optval = (int)r;
    *optlen = sizeof(int);
  }
  return 0;
}

int getsockname(int fd, struct sockaddr* addr, socklen_t* alen) {
  if (!is_managed_fd(fd)) return (int)sys_native(SYS_getsockname, fd, addr, alen);
  uint8_t out[8];
  uint32_t out_len = 0;
  int64_t args[6] = {fd, 0, 0, 0, 0, 0};
  int64_t r =
      ipc_call(SYS_getsockname, args, nullptr, 0, out, sizeof(out), &out_len);
  if (r < 0) return -1;
  if (out_len >= 6) {
    uint32_t ip;
    uint16_t port;
    memcpy(&ip, out, 4);
    memcpy(&port, out + 4, 2);
    fill_inet(addr, alen, ip, port);
  }
  return 0;
}

int getpeername(int fd, struct sockaddr* addr, socklen_t* alen) {
  if (!is_managed_fd(fd)) return (int)sys_native(SYS_getpeername, fd, addr, alen);
  uint8_t out[8];
  uint32_t out_len = 0;
  int64_t args[6] = {fd, 0, 0, 0, 0, 0};
  int64_t r =
      ipc_call(SYS_getpeername, args, nullptr, 0, out, sizeof(out), &out_len);
  if (r < 0) return -1;
  if (out_len >= 6) {
    uint32_t ip;
    uint16_t port;
    memcpy(&ip, out, 4);
    memcpy(&port, out + 4, 2);
    fill_inet(addr, alen, ip, port);
  }
  return 0;
}

int fcntl(int fd, int cmd, ...) {
  va_list ap;
  va_start(ap, cmd);
  long arg = va_arg(ap, long);
  va_end(ap);
  if (!is_managed_fd(fd)) return (int)sys_native(SYS_fcntl, fd, cmd, arg);
  return (int)ipc_call6(SYS_fcntl, fd, cmd, arg);
}

int ioctl(int fd, unsigned long req, ...) {
  va_list ap;
  va_start(ap, req);
  void* argp = va_arg(ap, void*);
  va_end(ap);
  if (!is_managed_fd(fd)) return (int)sys_native(SYS_ioctl, fd, req, argp);
  // FIONREAD is the one sockets commonly use
  int64_t r = ipc_call6(SYS_ioctl, fd, (int64_t)req);
  if (r < 0) return -1;
  if (argp) *(int*)argp = (int)r;
  return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// time virtualization (reference analog: shim_syscall.c time cache +
// clock_gettime interposition; sim time is authoritative)
// ---------------------------------------------------------------------------

extern "C" {

int clock_gettime(clockid_t clk, struct timespec* tp) {
  if (!g_ch) return (int)sys_native(SYS_clock_gettime, clk, tp);
  int64_t r = ipc_call6(SYS_clock_gettime, clk);
  if (r < 0) return -1;
  if (tp) {
    tp->tv_sec = r / 1000000000LL;
    tp->tv_nsec = r % 1000000000LL;
  }
  return 0;
}

int gettimeofday(struct timeval* tv, void* tz) {
  (void)tz;
  if (!g_ch) return (int)sys_native(SYS_gettimeofday, tv, tz);
  struct timespec ts;
  if (clock_gettime(CLOCK_REALTIME, &ts) != 0) return -1;
  if (tv) {
    tv->tv_sec = ts.tv_sec;
    tv->tv_usec = ts.tv_nsec / 1000;
  }
  return 0;
}

time_t time(time_t* t) {
  if (!g_ch) {
    struct timespec ts;
    sys_native(SYS_clock_gettime, CLOCK_REALTIME, &ts);
    if (t) *t = ts.tv_sec;
    return ts.tv_sec;
  }
  struct timespec ts;
  if (clock_gettime(CLOCK_REALTIME, &ts) != 0) return (time_t)-1;
  if (t) *t = ts.tv_sec;
  return ts.tv_sec;
}

int nanosleep(const struct timespec* req, struct timespec* rem) {
  if (!g_ch) return (int)sys_native(SYS_nanosleep, req, rem);
  if (!req) {
    errno = EFAULT;
    return -1;
  }
  int64_t ns = (int64_t)req->tv_sec * 1000000000LL + req->tv_nsec;
  int64_t r = ipc_call6(SYS_nanosleep, ns);
  if (rem) {
    rem->tv_sec = 0;
    rem->tv_nsec = 0;
  }
  return r < 0 ? -1 : 0;
}

unsigned int sleep(unsigned int seconds) {
  struct timespec ts = {(time_t)seconds, 0};
  nanosleep(&ts, nullptr);
  return 0;
}

int usleep(useconds_t usec) {
  struct timespec ts = {(time_t)(usec / 1000000),
                        (long)(usec % 1000000) * 1000};
  return nanosleep(&ts, nullptr);
}

// ---------------------------------------------------------------------------
// readiness: epoll / poll / select
// ---------------------------------------------------------------------------

int epoll_create1(int flags) {
  if (!g_ch) return (int)sys_native(SYS_epoll_create1, flags);
  return (int)ipc_call6(SYS_epoll_create1, flags);
}

int epoll_create(int size) {
  (void)size;
  return epoll_create1(0);
}

int epoll_ctl(int epfd, int op, int fd, struct epoll_event* ev) {
  if (!is_managed_fd(epfd))
    return (int)sys_native(SYS_epoll_ctl, epfd, op, fd, ev);
  int64_t events = ev ? (int64_t)ev->events : 0;
  int64_t data = ev ? (int64_t)ev->data.u64 : 0;
  return (int)ipc_call6(SYS_epoll_ctl, epfd, op, fd, events, data);
}

int epoll_wait(int epfd, struct epoll_event* evs, int maxevents,
               int timeout_ms) {
  if (!is_managed_fd(epfd))
    return (int)sys_native(SYS_epoll_wait, epfd, evs, maxevents, timeout_ms);
  // reply data = maxevents × {u32 events, u64 data} packed (12 bytes each)
  int want = maxevents;
  if (want > (int)(IPC_DATA_MAX / 12)) want = IPC_DATA_MAX / 12;
  int64_t args[6] = {epfd, want, timeout_ms, 0, 0, 0};
  static thread_local uint8_t tmp[IPC_DATA_MAX];
  uint32_t out_len = 0;
  int64_t r = ipc_call(SYS_epoll_wait, args, nullptr, 0, tmp, IPC_DATA_MAX,
                       &out_len);
  if (r < 0) return -1;
  int nready = (int)r;
  for (int i = 0; i < nready && (uint32_t)(i * 12 + 12) <= out_len; i++) {
    uint32_t e;
    uint64_t d;
    memcpy(&e, tmp + i * 12, 4);
    memcpy(&d, tmp + i * 12 + 4, 8);
    evs[i].events = e;
    evs[i].data.u64 = d;
  }
  return nready;
}

int poll(struct pollfd* fds, nfds_t nfds, int timeout_ms) {
  bool any_managed = false;
  for (nfds_t i = 0; i < nfds; i++)
    if (is_managed_fd(fds[i].fd)) any_managed = true;
  if (!any_managed) return (int)sys_native(SYS_poll, fds, nfds, timeout_ms);
  // request data = nfds × {i32 fd, i16 events} (6 bytes); native fds in a
  // mixed set are reported to the driver too (it treats them as never
  // ready — a documented v1 simplification).
  if (nfds > IPC_DATA_MAX / 6) nfds = IPC_DATA_MAX / 6;
  static thread_local uint8_t tmp[IPC_DATA_MAX];
  for (nfds_t i = 0; i < nfds; i++) {
    int32_t fd = fds[i].fd;
    int16_t ev = fds[i].events;
    memcpy(tmp + i * 6, &fd, 4);
    memcpy(tmp + i * 6 + 4, &ev, 2);
  }
  int64_t args[6] = {(int64_t)nfds, timeout_ms, 0, 0, 0, 0};
  static thread_local uint8_t out[IPC_DATA_MAX];
  uint32_t out_len = 0;
  int64_t r = ipc_call(SYS_poll, args, tmp, (uint32_t)(nfds * 6), out,
                       IPC_DATA_MAX, &out_len);
  if (r < 0) return -1;
  // reply data = nfds × i16 revents
  for (nfds_t i = 0; i < nfds && (uint32_t)(i * 2 + 2) <= out_len; i++) {
    int16_t rev;
    memcpy(&rev, out + i * 2, 2);
    fds[i].revents = rev;
  }
  return (int)r;
}

int select(int nfds, fd_set* rd, fd_set* wr, fd_set* ex,
           struct timeval* timeout) {
  bool any_managed = false;
  for (int fd = FD_BASE; fd < nfds; fd++) {
    if ((rd && FD_ISSET(fd, rd)) || (wr && FD_ISSET(fd, wr)) ||
        (ex && FD_ISSET(fd, ex)))
      any_managed = true;
  }
  if (!g_ch || !any_managed)
    return (int)sys_native(SYS_select, nfds, rd, wr, ex, timeout);
  // convert to a pollfd set over the managed fds, forward as poll
  struct pollfd pfds[64];
  int n = 0;
  for (int fd = 0; fd < nfds && n < 64; fd++) {
    short ev = 0;
    if (rd && FD_ISSET(fd, rd)) ev |= POLLIN;
    if (wr && FD_ISSET(fd, wr)) ev |= POLLOUT;
    if (ex && FD_ISSET(fd, ex)) ev |= POLLERR;
    if (ev) {
      pfds[n].fd = fd;
      pfds[n].events = ev;
      pfds[n].revents = 0;
      n++;
    }
  }
  int timeout_ms = -1;
  if (timeout)
    timeout_ms = (int)(timeout->tv_sec * 1000 + timeout->tv_usec / 1000);
  int r = poll(pfds, n, timeout_ms);
  if (r < 0) return -1;
  if (rd) FD_ZERO(rd);
  if (wr) FD_ZERO(wr);
  if (ex) FD_ZERO(ex);
  int count = 0;
  for (int i = 0; i < n; i++) {
    if (pfds[i].revents & (POLLIN | POLLHUP)) {
      if (rd) {
        FD_SET(pfds[i].fd, rd);
        count++;
      }
    }
    if (pfds[i].revents & POLLOUT) {
      if (wr) {
        FD_SET(pfds[i].fd, wr);
        count++;
      }
    }
    if (pfds[i].revents & POLLERR) {
      if (ex) {
        FD_SET(pfds[i].fd, ex);
        count++;
      }
    }
  }
  return count;
}

// ---------------------------------------------------------------------------
// name resolution (reference analog: preload_libraries.c getaddrinfo via a
// custom simulator-side resolution syscall + DNS registry in routing/dns.c)
// ---------------------------------------------------------------------------

int getaddrinfo(const char* node, const char* service,
                const struct addrinfo* hints, struct addrinfo** res) {
  if (!g_ch) return EAI_FAIL;  // no native fallback under the simulator
  uint32_t ip = 0;
  if (node) {
    struct in_addr a;
    if (inet_aton(node, &a)) {
      ip = ntohl(a.s_addr);
    } else {
      int64_t args[6] = {0, 0, 0, 0, 0, 0};
      int64_t r = ipc_call(PSYS_RESOLVE_NAME, args, node,
                           (uint32_t)strlen(node), nullptr, 0, nullptr);
      if (r < 0) return EAI_NONAME;
      ip = (uint32_t)r;
    }
  } else {
    ip = INADDR_LOOPBACK;
  }
  uint16_t port = 0;
  if (service) port = (uint16_t)atoi(service);

  struct addrinfo* ai = (struct addrinfo*)calloc(1, sizeof(struct addrinfo));
  struct sockaddr_in* sin =
      (struct sockaddr_in*)calloc(1, sizeof(struct sockaddr_in));
  sin->sin_family = AF_INET;
  sin->sin_addr.s_addr = htonl(ip);
  sin->sin_port = htons(port);
  ai->ai_family = AF_INET;
  ai->ai_socktype = hints ? hints->ai_socktype : SOCK_STREAM;
  ai->ai_protocol = hints ? hints->ai_protocol : 0;
  ai->ai_addrlen = sizeof(struct sockaddr_in);
  ai->ai_addr = (struct sockaddr*)sin;
  *res = ai;
  return 0;
}

void freeaddrinfo(struct addrinfo* res) {
  while (res) {
    struct addrinfo* next = res->ai_next;
    free(res->ai_addr);
    free(res);
    res = next;
  }
}

int gethostname(char* name, size_t len) {
  if (len == 0) {
    // len-1 below would underflow to SIZE_MAX and overrun a 0-byte buffer
    errno = EINVAL;
    return -1;
  }
  if (!g_ch) {
    struct utsname u;
    if (sys_native(SYS_uname, &u) != 0) return -1;
    size_t want = strlen(u.nodename);
    size_t m = want < len - 1 ? want : len - 1;
    memcpy(name, u.nodename, m);
    name[m] = 0;
    return 0;
  }
  static thread_local char tmp[256];
  uint32_t out_len = 0;
  int64_t args[6] = {0, 0, 0, 0, 0, 0};
  int64_t r = ipc_call(PSYS_GETHOSTNAME, args, nullptr, 0, tmp, sizeof(tmp),
                       &out_len);
  if (r < 0) return -1;
  size_t n = out_len < len - 1 ? out_len : len - 1;
  memcpy(name, tmp, n);
  name[n] = 0;
  return 0;
}

// ---------------------------------------------------------------------------
// Minimal /proc virtualization: the CPU-count pseudo-files. Apps (and
// glibc's __get_nprocs on /sys-reading versions) that COUNT CPUS from
// files must see the simulated host's count, not the real machine's.
// A matching open returns an anonymous memfd holding synthesized content;
// everything else opens natively. (Reference analog: Shadow does not
// virtualize /proc either, but its processes are pinned; our determinism
// story makes nproc part of the simulation contract — see
// sched_getaffinity above.)
// ---------------------------------------------------------------------------

long virt_cpu_file_open(const char* path) {
  // returns a ready-to-read fd, or -1 when the path is not virtualized
  if (!g_ch || !path) return -1;
  if (strcmp(path, "/proc/cpuinfo") != 0 &&
      strcmp(path, "/sys/devices/system/cpu/online") != 0 &&
      strcmp(path, "/sys/devices/system/cpu/possible") != 0)
    return -1;
  cpu_set_t s;
  CPU_ZERO(&s);
  int ncpu = 1;
  if (sched_getaffinity_raw(0, sizeof(s), &s) > 0) {
    int n = CPU_COUNT(&s);
    if (n > 0) ncpu = n;
  }
  char buf[4096];
  size_t off = 0;
  if (strcmp(path, "/proc/cpuinfo") == 0) {
    for (int i = 0; i < ncpu && off + 64 < sizeof(buf); i++)
      off += (size_t)snprintf(buf + off, sizeof(buf) - off,
                              "processor\t: %d\nmodel name\t: simulated\n\n",
                              i);
  } else {
    off = (size_t)(ncpu > 1
                       ? snprintf(buf, sizeof(buf), "0-%d\n", ncpu - 1)
                       : snprintf(buf, sizeof(buf), "0\n"));
  }
  long fd = shim_gate_syscall(SYS_memfd_create, (long)"cpu_virt", 0, 0, 0, 0,
                              0);
  if (fd < 0) return -1;
  size_t w = 0;
  while (w < off) {
    long r = shim_gate_syscall(SYS_write, fd, (long)(buf + w), off - w, 0, 0,
                               0);
    if (r <= 0) {
      shim_gate_syscall(SYS_close, fd, 0, 0, 0, 0, 0);
      return -1;
    }
    w += (size_t)r;
  }
  shim_gate_syscall(SYS_lseek, fd, 0, SEEK_SET, 0, 0, 0);
  return fd;
}

int uname(struct utsname* buf) {
  long r = sys_native(SYS_uname, buf);
  if (r < 0 || !g_ch || !buf) return r < 0 ? -1 : 0;
  // nodename must agree with the simulated hostname (gethostname above) —
  // apps commonly identify themselves via uname and the real machine's
  // name leaking in would break determinism comparisons across machines
  char hn[sizeof(buf->nodename)];
  if (gethostname(hn, sizeof(hn)) == 0) {
    memset(buf->nodename, 0, sizeof(buf->nodename));
    strncpy(buf->nodename, hn, sizeof(buf->nodename) - 1);
  }
  return 0;
}

int clock_nanosleep(clockid_t clk, int flags, const struct timespec* req,
                    struct timespec* rem) {
  if (!g_ch) {
    // clock_nanosleep returns the error value directly (no errno)
    long r = shim_gate_syscall(SYS_clock_nanosleep, clk, flags, (long)req,
                               (long)rem, 0, 0);
    return r < 0 ? (int)-r : 0;
  }
  if (!req) return EFAULT;  // clock_nanosleep returns the error directly
  if (flags & TIMER_ABSTIME) {
    struct timespec now;
    clock_gettime(clk, &now);
    int64_t d = ts_to_ns(req) - ts_to_ns(&now);
    if (d <= 0) return 0;
    struct timespec rel;
    ns_to_ts(d, &rel);
    return nanosleep(&rel, rem) < 0 ? errno : 0;
  }
  return nanosleep(req, rem) < 0 ? errno : 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// wider libc surface (VERDICT r4 #5): stat on managed fds, interface
// enumeration, deterministic localtime, and the memory-map policy.
// Reference analogs: syscall_handler.c stat dispatch rows,
// preload_libraries.c:31-652 (getifaddrs/localtime), and
// memory_manager/memory_mapper.rs:66-95 (mmap interception — here a
// policy refusal: plugin memory is process-local by design, so only
// sharing-capable mappings need denying).
// ---------------------------------------------------------------------------

extern "C" {

// raw-kernel convention (-errno) → libc convention (-1 + errno)
#define RAWRET_INV(call)                    \
  ({                                        \
    long _r = (long)(call);                 \
    if (_r < 0) {                           \
      errno = (int)-_r;                     \
      _r = -1;                              \
    }                                       \
    _r;                                     \
  })

int fstat(int fd, struct stat* st) {
  if (!is_managed_fd(fd))
    return (int)RAWRET_INV(sys_native(SYS_fstat, fd, st));
  int64_t kind = ipc_call6(PSYS_FSTAT, fd);
  if (kind < 0) return -1;  // errno set by ipc_call
  memset(st, 0, sizeof(*st));
  switch ((int)kind) {
    case FD_KIND_SOCKET:
      st->st_mode = S_IFSOCK | 0777;
      break;
    case FD_KIND_PIPE:
      st->st_mode = S_IFIFO | 0600;
      break;
    default:  // eventfd/timerfd/epoll present as anonymous inodes
      st->st_mode = S_IFCHR | 0600;
      break;
  }
  st->st_nlink = 1;
  st->st_blksize = 4096;
  return 0;
}

int fstat64(int fd, struct stat64* st) {
  return fstat(fd, (struct stat*)st);  // identical layout on x86_64
}

int statx(int dirfd, const char* path, int flags, unsigned int mask,
          struct statx* stx) {
  // modern glibc/Rust stat entry point; managed dirfd with an empty path
  // (AT_EMPTY_PATH) is an fstat in disguise. The NULL test must go
  // through a volatile copy: glibc declares the parameter nonnull, so
  // -O2 would otherwise DELETE the check — and the raw-trap route feeds
  // NULL here legitimately (statx(fd, NULL, AT_EMPTY_PATH, ...) is valid
  // since Linux 6.11).
  const char* volatile vpath = path;
  if (is_managed_fd(dirfd) && (flags & AT_EMPTY_PATH) &&
      (vpath == nullptr || vpath[0] == 0)) {
    struct stat st;
    if (fstat(dirfd, &st) != 0) return -1;
    memset(stx, 0, sizeof(*stx));
    stx->stx_mask = STATX_TYPE | STATX_MODE | STATX_NLINK;
    stx->stx_mode = (uint16_t)st.st_mode;
    stx->stx_nlink = (uint32_t)st.st_nlink;
    stx->stx_blksize = (uint32_t)st.st_blksize;
    return 0;
  }
  return (int)RAWRET_INV(sys_native(SYS_statx, dirfd, path, flags, mask,
                                    stx));
}

int fstatat(int dirfd, const char* path, struct stat* st, int flags) {
  if (is_managed_fd(dirfd) && (!path || !path[0]))
    return fstat(dirfd, st);  // AT_EMPTY_PATH form glibc uses for fstat
  // sys_native (the IP-whitelisted gate), NEVER plain syscall(): the raw
  // instruction would re-trap the seccomp filter forever — and the FD0
  // discriminator compares arg0 low-32 UNSIGNED, so AT_FDCWD (-100)
  // traps every path-based stat through here
  return (int)RAWRET_INV(sys_native(SYS_newfstatat, dirfd, path, st,
                                    flags));
}

// Interface enumeration (preload_libraries.c getifaddrs analog): lo plus
// one eth0 carrying this host's simulated address. Allocated as a single
// block; freeifaddrs releases it whole.
struct ShimIfBlock {
  struct ifaddrs ifa[2];
  struct sockaddr_in addr[2];
  struct sockaddr_in mask[2];
  struct sockaddr_in bcast[2];
  char names[2][8];
};

int getifaddrs(struct ifaddrs** out) {
  if (!g_ch) {
    errno = ENOSYS;  // no native fallback under the simulator
    return -1;
  }
  char host[256];
  if (gethostname(host, sizeof host) != 0) return -1;
  int64_t args[6] = {0, 0, 0, 0, 0, 0};
  int64_t ip = ipc_call(PSYS_RESOLVE_NAME, args, host,
                        (uint32_t)strlen(host), nullptr, 0, nullptr);
  if (ip < 0) return -1;
  ShimIfBlock* b = (ShimIfBlock*)calloc(1, sizeof(ShimIfBlock));
  if (!b) {
    errno = ENOMEM;
    return -1;
  }
  strcpy(b->names[0], "lo");
  strcpy(b->names[1], "eth0");
  uint32_t ips[2] = {INADDR_LOOPBACK, (uint32_t)ip};
  uint32_t masks[2] = {0xFF000000u, 0xFFFFFF00u};
  unsigned int fl[2] = {IFF_UP | IFF_RUNNING | IFF_LOOPBACK,
                        IFF_UP | IFF_RUNNING | IFF_BROADCAST};
  for (int i = 0; i < 2; i++) {
    b->addr[i].sin_family = AF_INET;
    b->addr[i].sin_addr.s_addr = htonl(ips[i]);
    b->mask[i].sin_family = AF_INET;
    b->mask[i].sin_addr.s_addr = htonl(masks[i]);
    b->bcast[i].sin_family = AF_INET;
    b->bcast[i].sin_addr.s_addr = htonl(ips[i] | ~masks[i]);
    b->ifa[i].ifa_name = b->names[i];
    b->ifa[i].ifa_flags = fl[i];
    b->ifa[i].ifa_addr = (struct sockaddr*)&b->addr[i];
    b->ifa[i].ifa_netmask = (struct sockaddr*)&b->mask[i];
    if (fl[i] & IFF_BROADCAST)  // contract: broadaddr valid when flagged
      b->ifa[i].ifa_broadaddr = (struct sockaddr*)&b->bcast[i];
    b->ifa[i].ifa_next = i == 0 ? &b->ifa[1] : nullptr;
  }
  *out = &b->ifa[0];
  return 0;
}

void freeifaddrs(struct ifaddrs* ifa) {
  free(ifa);  // head of the single ShimIfBlock allocation
}

// Deterministic local time (preload_libraries.c localtime analog): the
// simulated clock is already served by time()/clock_gettime(); pinning the
// zone to UTC removes the host machine's /etc/localtime from results, so
// runs reproduce across machines.
struct tm* localtime_r(const time_t* t, struct tm* out) {
  return gmtime_r(t, out);
}

struct tm* localtime(const time_t* t) {
  static thread_local struct tm buf;
  return gmtime_r(t, &buf);
}

// Memory-map policy (memory_mapper.rs:66-95 analog, inverted: the
// reference remaps plugin memory into the simulator; here plugin memory
// is process-local by design, so mmap runs native EXCEPT where a mapping
// could smuggle nondeterministic shared state past the simulated I/O
// plane: writable file-backed MAP_SHARED is refused, and managed fds are
// not mappable at all. The shim's own channel mappings use raw syscalls
// and bypass this.
void* mmap(void* addr, size_t len, int prot, int flags, int fd, off_t off) {
  if (g_ch) {
    if (is_managed_fd(fd)) {
      errno = ENODEV;
      return MAP_FAILED;
    }
    if (fd >= 0 && (flags & MAP_SHARED) && (prot & PROT_WRITE)) {
      SHIM_LOG("mmap policy: refusing writable MAP_SHARED of fd %d", fd);
      errno = EACCES;
      return MAP_FAILED;
    }
    if (fd < 0 && (flags & MAP_SHARED) && (flags & MAP_ANONYMOUS) &&
        (prot & PROT_WRITE)) {
      // Consistent policy (ADVICE r4): a fork-inherited anonymous shared
      // mapping is exactly the cross-process shared-state channel the
      // file-backed refusal exists to deny — an app coordinating through
      // it would bypass the simulated I/O plane just the same.
      SHIM_LOG("mmap policy: refusing writable anonymous MAP_SHARED");
      errno = EACCES;
      return MAP_FAILED;
    }
  }
  return (void*)sys_native(SYS_mmap, (long)addr, (long)len, (long)prot,
                           (long)flags, (long)fd, (long)off);
}

void* mmap64(void* addr, size_t len, int prot, int flags, int fd,
             off64_t off) {
  return mmap(addr, len, prot, flags, fd, (off_t)off);
}

// ---------------------------------------------------------------------------
// /proc/self/fd DIRECTORY LISTING with managed fds merged in: the kernel's
// listing only shows real fds, so an app enumerating its descriptors (fd
// hygiene sweeps, close-range fallbacks) would miss every simulated
// socket/pipe/timer. opendir on the fd directory returns a synthetic
// stream of real entries (from the kernel) plus the driver's open managed
// fds (PSYS_FD_LIST). glibc-INTERNAL opendir calls (e.g. scandir) bypass
// PLT interposition and still see only real fds — documented limitation.
// ---------------------------------------------------------------------------

struct VirtFdDir {
  long fds[1024];
  int count;
  int pos;
  int backing_fd;  // real O_DIRECTORY fd: dirfd() identity for skip logic
  struct dirent ent;
};

// Registry slots are atomics: readdir/closedir on ORDINARY directory
// streams must not take a process-wide lock — the hot-path membership
// check is a handful of relaxed loads; the mutex only serializes open
// registration.
static std::atomic<VirtFdDir*> g_vdirs[64];
static pthread_mutex_t g_vdir_mu = PTHREAD_MUTEX_INITIALIZER;

static bool is_proc_fd_dir(const char* name) {
  if (!name) return false;
  if (strcmp(name, "/proc/self/fd") == 0 ||
      strcmp(name, "/proc/self/fd/") == 0 ||
      strcmp(name, "/dev/fd") == 0 ||  // the portable alias (symlink to
      strcmp(name, "/dev/fd/") == 0)   // /proc/self/fd; BSD-derived code)
    return true;
  char buf[64];
  snprintf(buf, sizeof buf, "/proc/%d/fd", (int)getpid());
  size_t n = strlen(buf);
  return strncmp(name, buf, n) == 0 &&
         (name[n] == 0 || (name[n] == '/' && name[n + 1] == 0));
}

static VirtFdDir* vdir_of(DIR* dp) {
  for (auto& slot : g_vdirs)
    if (slot.load(std::memory_order_relaxed) == (VirtFdDir*)dp)
      return (VirtFdDir*)dp;
  return nullptr;
}

DIR* opendir(const char* name) {
  static auto real_opendir = (DIR * (*)(const char*)) dlsym(RTLD_NEXT,
                                                            "opendir");
  static auto real_readdir =
      (struct dirent * (*)(DIR*)) dlsym(RTLD_NEXT, "readdir");
  static auto real_closedir = (int (*)(DIR*))dlsym(RTLD_NEXT, "closedir");
  if (!g_ch || !is_proc_fd_dir(name)) return real_opendir(name);
  VirtFdDir* d = (VirtFdDir*)calloc(1, sizeof(VirtFdDir));
  if (!d) return nullptr;
  // real directory fd FIRST: dirfd() must return a live fd that appears
  // in the listing, exactly like a kernel DIR (fd-hygiene sweeps skip it)
  d->backing_fd = (int)sys_native(SYS_open, (long)name,
                                  O_RDONLY | O_DIRECTORY, 0);
  DIR* rd = real_opendir(name);
  if (rd) {
    struct dirent* e;
    while ((e = real_readdir(rd)) && d->count < 1000) {
      if (e->d_name[0] == '.') continue;
      char* end = nullptr;
      long fd = strtol(e->d_name, &end, 10);
      if (end && *end == 0) d->fds[d->count++] = fd;
    }
    real_closedir(rd);
  }
  int64_t args[6] = {0, 0, 0, 0, 0, 0};
  static thread_local uint8_t out[IPC_DATA_MAX];
  uint32_t out_len = 0;
  int64_t r = ipc_call(PSYS_FD_LIST, args, nullptr, 0, out, IPC_DATA_MAX,
                       &out_len);
  for (int i = 0; r > 0 && i < (int)r && d->count < 1024 &&
                  (uint32_t)(i * 4 + 4) <= out_len;
       i++) {
    int32_t fd;
    memcpy(&fd, out + i * 4, 4);
    d->fds[d->count++] = fd;
  }
  bool registered = false;
  pthread_mutex_lock(&g_vdir_mu);
  for (auto& slot : g_vdirs)
    if (slot.load(std::memory_order_relaxed) == nullptr) {
      slot.store(d, std::memory_order_release);
      registered = true;
      break;
    }
  pthread_mutex_unlock(&g_vdir_mu);
  if (!registered) {
    // registry exhausted: the kernel-only view would NONDETERMINISTICALLY
    // hide managed fds depending on open-stream count — be loud about it
    SHIM_LOG("opendir(%s): virtual-dir registry full (64 streams); "
             "falling back to the kernel view WITHOUT managed fds", name);
    if (d->backing_fd >= 0) sys_native(SYS_close, d->backing_fd);
    free(d);
    return real_opendir(name);
  }
  return (DIR*)d;
}

int dirfd(DIR* dp) {
  static auto real_dirfd = (int (*)(DIR*))dlsym(RTLD_NEXT, "dirfd");
  VirtFdDir* d = vdir_of(dp);
  if (!d) return real_dirfd(dp);
  if (d->backing_fd < 0) {
    errno = EINVAL;
    return -1;
  }
  return d->backing_fd;
}

void rewinddir(DIR* dp) {
  static auto real_rewinddir = (void (*)(DIR*))dlsym(RTLD_NEXT, "rewinddir");
  VirtFdDir* d = vdir_of(dp);
  if (!d) {
    real_rewinddir(dp);
    return;
  }
  d->pos = 0;  // replay the open-time snapshot (proc listings are
               // snapshots under the kernel too)
}

long telldir(DIR* dp) {
  static auto real_telldir = (long (*)(DIR*))dlsym(RTLD_NEXT, "telldir");
  VirtFdDir* d = vdir_of(dp);
  if (!d) return real_telldir(dp);
  return d->pos;
}

void seekdir(DIR* dp, long loc) {
  static auto real_seekdir = (void (*)(DIR*, long))dlsym(RTLD_NEXT,
                                                         "seekdir");
  VirtFdDir* d = vdir_of(dp);
  if (!d) {
    real_seekdir(dp, loc);
    return;
  }
  if (loc >= 0 && loc <= d->count) d->pos = (int)loc;
}

struct dirent* readdir(DIR* dp) {
  static auto real_readdir =
      (struct dirent * (*)(DIR*)) dlsym(RTLD_NEXT, "readdir");
  VirtFdDir* d = vdir_of(dp);
  if (!d) return real_readdir(dp);
  if (d->pos >= d->count) return nullptr;
  long fd = d->fds[d->pos++];
  memset(&d->ent, 0, sizeof(d->ent));
  d->ent.d_ino = (ino_t)(fd + 1);
  d->ent.d_type = DT_LNK;  // proc fd entries are magic symlinks
  snprintf(d->ent.d_name, sizeof(d->ent.d_name), "%ld", fd);
  return &d->ent;
}

struct dirent64* readdir64(DIR* dp) {
  static auto real_readdir64 =
      (struct dirent64 * (*)(DIR*)) dlsym(RTLD_NEXT, "readdir64");
  VirtFdDir* d = vdir_of(dp);
  if (!d) return real_readdir64(dp);
  // x86_64 glibc: dirent and dirent64 share the layout
  return (struct dirent64*)readdir(dp);
}

int closedir(DIR* dp) {
  static auto real_closedir = (int (*)(DIR*))dlsym(RTLD_NEXT, "closedir");
  VirtFdDir* d = vdir_of(dp);
  if (!d) return real_closedir(dp);
  pthread_mutex_lock(&g_vdir_mu);
  for (auto& slot : g_vdirs)
    if (slot.load(std::memory_order_relaxed) == d)
      slot.store(nullptr, std::memory_order_release);
  pthread_mutex_unlock(&g_vdir_mu);
  if (d->backing_fd >= 0) sys_native(SYS_close, d->backing_fd);
  free(d);
  return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// seccomp/SIGSYS backstop (reference analog: shim.c:399-463): raw syscall
// instructions that bypass the interposed libc symbols trap to SIGSYS and
// are routed through the same wrappers. Only the emulated syscall numbers
// trap; everything else — and anything issued from the gate — is allowed.
// ---------------------------------------------------------------------------

namespace {

// libc-convention wrapper result → raw-kernel convention (-errno)
#define RAWRET(call)                        \
  ({                                        \
    long _r = (long)(call);                 \
    _r < 0 ? -(long)errno : _r;             \
  })

// /proc/self/fd/<n> for a MANAGED n: reopening one's own descriptor is a
// dup of the open description (the kernel's magic-symlink semantics for
// pipes/sockets reduce to that here). Returns LONG_MIN when the path is
// not a managed /proc/self/fd entry (caller falls through to native).
long virt_proc_fd_open(const char* path) {
  if (!path) return LONG_MIN;
  const char* num = nullptr;
  if (strncmp(path, "/proc/self/fd/", 14) == 0)
    num = path + 14;
  else if (strncmp(path, "/dev/fd/", 8) == 0)  // portable alias
    num = path + 8;
  else
    return LONG_MIN;
  char* end = nullptr;
  long n = strtol(num, &end, 10);
  if (!end || *end != 0 || n < FD_BASE) return LONG_MIN;
  return RAWRET(dup((int)n));
}

long route_raw_syscall(long nr, long a0, long a1, long a2, long a3, long a4,
                       long a5) {
  switch (nr) {
    case SYS_socket:
      return RAWRET(socket((int)a0, (int)a1, (int)a2));
    case SYS_bind:
      return RAWRET(bind((int)a0, (const struct sockaddr*)a1, (socklen_t)a2));
    case SYS_listen:
      return RAWRET(listen((int)a0, (int)a1));
    case SYS_connect:
      return RAWRET(
          connect((int)a0, (const struct sockaddr*)a1, (socklen_t)a2));
    case SYS_accept:
      return RAWRET(
          accept4((int)a0, (struct sockaddr*)a1, (socklen_t*)a2, 0));
    case SYS_accept4:
      return RAWRET(
          accept4((int)a0, (struct sockaddr*)a1, (socklen_t*)a2, (int)a3));
    case SYS_sendto:
      return RAWRET(sendto((int)a0, (const void*)a1, (size_t)a2, (int)a3,
                           (const struct sockaddr*)a4, (socklen_t)a5));
    case SYS_recvfrom:
      return RAWRET(recvfrom((int)a0, (void*)a1, (size_t)a2, (int)a3,
                             (struct sockaddr*)a4, (socklen_t*)a5));
    case SYS_sendmsg:
      return RAWRET(sendmsg((int)a0, (const struct msghdr*)a1, (int)a2));
    case SYS_recvmsg:
      return RAWRET(recvmsg((int)a0, (struct msghdr*)a1, (int)a2));
    case SYS_shutdown:
      return RAWRET(shutdown((int)a0, (int)a1));
    case SYS_getsockname:
      return RAWRET(
          getsockname((int)a0, (struct sockaddr*)a1, (socklen_t*)a2));
    case SYS_getpeername:
      return RAWRET(
          getpeername((int)a0, (struct sockaddr*)a1, (socklen_t*)a2));
    case SYS_setsockopt:
      return RAWRET(setsockopt((int)a0, (int)a1, (int)a2, (const void*)a3,
                               (socklen_t)a4));
    case SYS_getsockopt:
      return RAWRET(
          getsockopt((int)a0, (int)a1, (int)a2, (void*)a3, (socklen_t*)a4));
    case SYS_read:
      return RAWRET(read((int)a0, (void*)a1, (size_t)a2));
    case SYS_write:
      return RAWRET(write((int)a0, (const void*)a1, (size_t)a2));
    case SYS_readv:
      return RAWRET(readv((int)a0, (const struct iovec*)a1, (int)a2));
    case SYS_writev:
      return RAWRET(writev((int)a0, (const struct iovec*)a1, (int)a2));
    case SYS_close:
      return RAWRET(close((int)a0));
    case SYS_dup:
      return RAWRET(dup((int)a0));
    case SYS_dup2:
      return RAWRET(dup2((int)a0, (int)a1));
    case SYS_dup3:
      return RAWRET(dup3((int)a0, (int)a1, (int)a2));
    case SYS_fcntl:
      return RAWRET(fcntl((int)a0, (int)a1, a2));
    case SYS_ioctl:
      return RAWRET(ioctl((int)a0, (unsigned long)a1, (void*)a2));
    case SYS_pipe: {
      return RAWRET(pipe2((int*)a0, 0));
    }
    case SYS_pipe2:
      return RAWRET(pipe2((int*)a0, (int)a1));
    case SYS_eventfd:
      return RAWRET(eventfd((unsigned int)a0, 0));
    case SYS_eventfd2:
      return RAWRET(eventfd((unsigned int)a0, (int)a1));
    case SYS_timerfd_create:
      return RAWRET(timerfd_create((int)a0, (int)a1));
    case SYS_timerfd_settime:
      return RAWRET(timerfd_settime((int)a0, (int)a1,
                                    (const struct itimerspec*)a2,
                                    (struct itimerspec*)a3));
    case SYS_timerfd_gettime:
      return RAWRET(timerfd_gettime((int)a0, (struct itimerspec*)a1));
    case SYS_epoll_create:
    case SYS_epoll_create1:
      return RAWRET(epoll_create1(nr == SYS_epoll_create ? 0 : (int)a0));
    case SYS_epoll_ctl:
      return RAWRET(
          epoll_ctl((int)a0, (int)a1, (int)a2, (struct epoll_event*)a3));
    case SYS_epoll_wait:
      return RAWRET(
          epoll_wait((int)a0, (struct epoll_event*)a1, (int)a2, (int)a3));
    case SYS_epoll_pwait:
      return RAWRET(epoll_pwait((int)a0, (struct epoll_event*)a1, (int)a2,
                                (int)a3, (const sigset_t*)a4));
    case SYS_poll:
      return RAWRET(poll((struct pollfd*)a0, (nfds_t)a1, (int)a2));
    case SYS_ppoll:
      return RAWRET(ppoll((struct pollfd*)a0, (nfds_t)a1,
                          (const struct timespec*)a2,
                          (const sigset_t*)a3));
    case SYS_signalfd:
      return RAWRET(signalfd((int)a0, (const sigset_t*)a1, 0));
    case SYS_signalfd4:
      return RAWRET(signalfd((int)a0, (const sigset_t*)a1, (int)a3));
    case SYS_getrlimit:
      return RAWRET(getrlimit((int)a0, (struct rlimit*)a1));
    case SYS_setrlimit:
      return RAWRET(setrlimit((int)a0, (const struct rlimit*)a1));
    case SYS_prlimit64:
      return RAWRET(prlimit((pid_t)a0, (__rlimit_resource)a1,
                            (const struct rlimit*)a2, (struct rlimit*)a3));
    case SYS_getrusage:
      return RAWRET(getrusage((int)a0, (struct rusage*)a1));
    case SYS_select:
      return RAWRET(select((int)a0, (fd_set*)a1, (fd_set*)a2, (fd_set*)a3,
                           (struct timeval*)a4));
    case SYS_pselect6: {
      // the kernel ABI's 6th arg is {const sigset_t*, size_t}
      struct KernelSigset {
        const sigset_t* ss;
        size_t len;
      };
      const KernelSigset* sm = (const KernelSigset*)a5;
      return RAWRET(pselect((int)a0, (fd_set*)a1, (fd_set*)a2, (fd_set*)a3,
                            (const struct timespec*)a4,
                            sm ? sm->ss : nullptr));
    }
    case SYS_clock_gettime:
      return RAWRET(clock_gettime((clockid_t)a0, (struct timespec*)a1));
    case SYS_gettimeofday:
      return RAWRET(gettimeofday((struct timeval*)a0, (void*)a1));
    case SYS_time: {
      time_t t = time((time_t*)a0);
      return (long)t;
    }
    case SYS_nanosleep:
      return RAWRET(
          nanosleep((const struct timespec*)a0, (struct timespec*)a1));
    case SYS_clock_nanosleep: {
      int e = clock_nanosleep((clockid_t)a0, (int)a1,
                              (const struct timespec*)a2,
                              (struct timespec*)a3);
      return -(long)e;  // clock_nanosleep returns the errno directly
    }
    case SYS_getrandom:
      return RAWRET(getrandom((void*)a0, (size_t)a1, (unsigned int)a2));
    case SYS_sched_getaffinity:
      if (!g_ch) return shim_gate_syscall(nr, a0, a1, a2, a3, a4, a5);
      return sched_getaffinity_raw((pid_t)a0, (size_t)a1, (cpu_set_t*)a2);
    case SYS_fstat:
      return RAWRET(fstat((int)a0, (struct stat*)a1));
    case SYS_mmap: {
      void* r = mmap((void*)a0, (size_t)a1, (int)a2, (int)a3, (int)a4,
                     (off_t)a5);
      return r == MAP_FAILED ? -(long)errno : (long)r;
    }
    case SYS_newfstatat:
      return RAWRET(fstatat((int)a0, (const char*)a1, (struct stat*)a2,
                            (int)a3));
    case SYS_statx:
      return RAWRET(statx((int)a0, (const char*)a1, (int)a2,
                          (unsigned int)a3, (struct statx*)a4));
    case SYS_open: {
      long vfd = virt_cpu_file_open((const char*)a0);
      if (vfd >= 0) return vfd;
      vfd = virt_proc_fd_open((const char*)a0);
      if (vfd != LONG_MIN) return vfd;
      return shim_gate_syscall(nr, a0, a1, a2, a3, a4, a5);
    }
    case SYS_openat: {
      const char* p = (const char*)a1;
      if (p && p[0] == '/') {
        long vfd = virt_cpu_file_open(p);
        if (vfd >= 0) return vfd;
        vfd = virt_proc_fd_open(p);
        if (vfd != LONG_MIN) return vfd;
      }
      return shim_gate_syscall(nr, a0, a1, a2, a3, a4, a5);
    }
    default:
      return shim_gate_syscall(nr, a0, a1, a2, a3, a4, a5);
  }
}

void on_sigsys(int sig, siginfo_t* info, void* vctx) {
  (void)sig;
#if defined(__x86_64__)
  ucontext_t* uc = (ucontext_t*)vctx;
  greg_t* g = uc->uc_mcontext.gregs;
  long nr = (long)info->si_syscall;
  // Recursion guard for exec'd images: an INHERITED filter from the
  // pre-exec image traps even this image's gate (different address), so a
  // native-fallback path would re-trap forever. Depth >= 2 on the same
  // thread means exactly that — fail the syscall loudly instead.
  static __thread int depth = 0;
  if (depth >= 2) {
    g[REG_RAX] = (greg_t)(-ENOSYS);
    return;
  }
  depth++;
  long r = route_raw_syscall(nr, g[REG_RDI], g[REG_RSI], g[REG_RDX],
                             g[REG_R10], g[REG_R8], g[REG_R9]);
  depth--;
  g[REG_RAX] = (greg_t)r;
#else
  (void)info;
  (void)vctx;
#endif
}

// syscall numbers the backstop traps (the emulated surface; everything
// else — memory, threads, files, process control — passes through)
// Trap classification. FD0/FD01 syscalls trap ONLY when the fd argument
// is in the emulated range (>= FD_BASE): low/real-fd operations run native
// with zero filter cost, and — crucially — an EXEC'D image (which inherits
// this filter but starts with no SIGSYS handler until its own shim
// constructor runs) can boot: ld.so/libc startup only touches low fds.
enum TrapAct { ACT_TRAP, ACT_FD0, ACT_FD01 };
struct TrapEntry {
  int nr;
  TrapAct act;
};
const TrapEntry kTrapped[] = {
    {SYS_read, ACT_FD0},          {SYS_write, ACT_FD0},
    {SYS_close, ACT_FD0},         {SYS_poll, ACT_TRAP},
    {SYS_ioctl, ACT_FD0},         {SYS_readv, ACT_FD0},
    {SYS_writev, ACT_FD0},        {SYS_select, ACT_TRAP},
    {SYS_dup, ACT_FD0},           {SYS_dup2, ACT_FD01},
    {SYS_dup3, ACT_FD01},         {SYS_nanosleep, ACT_TRAP},
    {SYS_socket, ACT_TRAP},       {SYS_connect, ACT_FD0},
    {SYS_accept, ACT_FD0},        {SYS_accept4, ACT_FD0},
    {SYS_sendto, ACT_FD0},        {SYS_recvfrom, ACT_FD0},
    {SYS_sendmsg, ACT_FD0},       {SYS_recvmsg, ACT_FD0},
    {SYS_shutdown, ACT_FD0},      {SYS_bind, ACT_FD0},
    {SYS_listen, ACT_FD0},        {SYS_getsockname, ACT_FD0},
    {SYS_getpeername, ACT_FD0},   {SYS_setsockopt, ACT_FD0},
    {SYS_getsockopt, ACT_FD0},    {SYS_fcntl, ACT_FD0},
    {SYS_gettimeofday, ACT_TRAP}, {SYS_time, ACT_TRAP},
    {SYS_clock_gettime, ACT_TRAP}, {SYS_clock_nanosleep, ACT_TRAP},
    {SYS_epoll_create, ACT_TRAP}, {SYS_epoll_create1, ACT_TRAP},
    {SYS_epoll_ctl, ACT_FD0},     {SYS_epoll_wait, ACT_FD0},
    {SYS_epoll_pwait, ACT_FD0},   {SYS_timerfd_create, ACT_TRAP},
    {SYS_timerfd_settime, ACT_FD0}, {SYS_timerfd_gettime, ACT_FD0},
    {SYS_eventfd, ACT_TRAP},      {SYS_eventfd2, ACT_TRAP},
    {SYS_pipe, ACT_TRAP},         {SYS_pipe2, ACT_TRAP},
    {SYS_getrandom, ACT_TRAP},    {SYS_pselect6, ACT_TRAP},
    {SYS_sched_getaffinity, ACT_TRAP},
    // signal-plane descriptors + mask-swapping waits ride the virtual
    // signal tables; resource limits/usage are deterministic synthesis
    {SYS_signalfd, ACT_TRAP},     {SYS_signalfd4, ACT_TRAP},
    {SYS_ppoll, ACT_TRAP},
    {SYS_getrlimit, ACT_TRAP},    {SYS_setrlimit, ACT_TRAP},
    {SYS_prlimit64, ACT_TRAP},    {SYS_getrusage, ACT_TRAP},
    // opens trap so CPU-count pseudo-files virtualize even through
    // glibc-internal (non-PLT) calls; non-matching paths re-enter the
    // kernel through the gate — one SIGSYS round trip per open
    {SYS_open, ACT_TRAP},         {SYS_openat, ACT_TRAP},
    // stat family: managed fds present synthesized metadata (PSYS_FSTAT);
    // newfstatat discriminates on dirfd (AT_EMPTY_PATH fstat form)
    {SYS_fstat, ACT_FD0},         {SYS_newfstatat, ACT_FD0},
    {SYS_statx, ACT_FD0},
    // mmap policy (writable file-backed MAP_SHARED refused) must hold
    // for raw/glibc-internal calls too; the shim's own channel maps go
    // through the gate and are exempt
    {SYS_mmap, ACT_TRAP},
};

}  // namespace

// ---------------------------------------------------------------------------
// threads, futexes, fork (reference analogs: thread_preload.c:358-400 clone
// bootstrap, futex.c/syscall/futex.c, process.c:460-531). Execution model:
// the driver runs AT MOST ONE thread of a process between syscalls (it
// withholds wake replies until the running thread blocks), which makes
// multithreaded apps deterministic. Blocking synchronization therefore must
// never block NATIVELY (a native futex wait would wedge the whole process):
// the pthread mutex/cond surface is interposed here and parks threads in
// the DRIVER, keyed by futex word address. The shim reads/writes the words
// directly — same address space, no remote memory manager needed.
// ---------------------------------------------------------------------------

namespace {

Channel* map_channel(const char* path) {
  int fd = open(path, O_RDWR);
  if (fd < 0) return nullptr;
  // raw syscall: the libc-visible mmap wrapper (below) denies writable
  // MAP_SHARED file mappings as policy, and must not deny our own channels
  void* p = (void*)sys_native(SYS_mmap, (long)nullptr, sizeof(Channel),
                              PROT_READ | PROT_WRITE, MAP_SHARED, fd, 0);
  close(fd);
  if (p == MAP_FAILED || ((Channel*)p)->magic != IPC_MAGIC) return nullptr;
  return (Channel*)p;
}

// Registered via on_exit in shim_init: catches BOTH explicit exit() and
// return-from-main (glibc calls exit internally, bypassing any interposed
// exit symbol). The driver needs this DETERMINISTIC, sim-time-stamped
// process-done signal — fork children have no popen handle to poll, and a
// parent parked in waitpid must wake at a well-defined virtual instant.
// (_exit/_Exit bypass atexit and so skip this — the driver's STOP path
// uses _exit precisely to avoid a re-entrant notification.)
void shim_notify_exit(int status, void*) {
  if (!g_ch) return;
  int64_t a[6] = {status, 1 /* process-level */, 0, 0, 0, 0};
  ipc_call(PSYS_THREAD_EXIT, a, nullptr, 0, nullptr, 0, nullptr);
  g_ch = nullptr;
  t_ch = nullptr;
}

int futex_wait_driver(const void* uaddr, int64_t timeout_ns) {
  int64_t a[6] = {(int64_t)(uintptr_t)uaddr, timeout_ns, 0, 0, 0, 0};
  int64_t r = ipc_call(PSYS_FUTEX_WAIT, a, nullptr, 0, nullptr, 0, nullptr);
  return r < 0 ? (int)errno : 0;
}

void futex_wake_driver(const void* uaddr, int n) {
  int64_t a[6] = {(int64_t)(uintptr_t)uaddr, n, 0, 0, 0, 0};
  ipc_call(PSYS_FUTEX_WAKE, a, nullptr, 0, nullptr, 0, nullptr);
}

struct ThreadReg {
  ThreadReg* next;
  pthread_t handle;
  Channel* ch;
  void* (*fn)(void*);
  void* arg;
  char shm[160];
  std::atomic<int> done;
};
ThreadReg* g_threads = nullptr;
std::atomic_flag g_threads_lock = ATOMIC_FLAG_INIT;
__thread ThreadReg* t_reg = nullptr;

void thread_epilogue() {
  // done-flag + joiner wake + driver notification; runs exactly once per
  // managed thread, whether it returns from its start routine or calls
  // pthread_exit (which is interposed to come through here)
  ThreadReg* r = t_reg;
  if (!r) return;
  t_reg = nullptr;
  r->done.store(1, std::memory_order_release);
  futex_wake_driver(&r->done, INT32_MAX);  // joiners
  int64_t a[6] = {0, 0, 0, 0, 0, 0};
  ipc_call(PSYS_THREAD_EXIT, a, nullptr, 0, nullptr, 0, nullptr);
  t_ch = nullptr;
}

void* thread_tramp(void* vp) {
#if defined(__x86_64__)
  // PR_SET_TSC is per-thread: new threads must trap rdtsc too (only if
  // the process-wide SIGSEGV emulator is actually installed)
  if (g_tsc_trap_on) prctl(PR_SET_TSC, PR_TSC_SIGSEGV, 0, 0, 0);
#endif
  ThreadReg* r = (ThreadReg*)vp;
  Channel* ch = map_channel(r->shm);
  if (ch) {
    t_ch = ch;
    r->ch = ch;
    ch->shim_pid = (int32_t)sys_native(SYS_gettid);
    // HELLO on the thread's own channel; the driver admits this thread
    // (replies) only once the spawner blocks — one-at-a-time execution
    ch->type = MSG_HELLO;
    ch->ret = ch->shim_pid;
    ch->data_len = 0;
    sem_post(&ch->to_driver);
    sem_wait_spinning(&ch->to_shim, g_spin);
  } else {
    SHIM_LOG("thread channel %s failed to map; thread runs unmanaged",
             r->shm);
  }
  t_reg = r;
  void* rv = r->fn(r->arg);
  thread_epilogue();
  return rv;
}

ThreadReg* find_thread(pthread_t h) {
  raw_lock(&g_threads_lock);
  ThreadReg* r = g_threads;
  while (r && !pthread_equal(r->handle, h)) r = r->next;
  raw_unlock(&g_threads_lock);
  return r;
}

// glibc struct __pthread_mutex_s prefix (x86-64): the interposed mutex
// surface owns the semantics, reusing the same fields
struct MutexView {
  int lock;        // futex word: 0 free, 1 locked, 2 locked+waiters
  unsigned count;  // recursion count
  int owner;       // tid
  unsigned nusers;
  int kind;        // PTHREAD_MUTEX_* from pthread_mutex_init (glibc's)
};

int my_tid() {
  static __thread int tid = 0;
  if (!tid) tid = (int)sys_native(SYS_gettid);
  return tid;
}

}  // namespace

extern "C" {

int pthread_create(pthread_t* out, const pthread_attr_t* attr,
                   void* (*fn)(void*), void* arg) {
  static auto real = (int (*)(pthread_t*, const pthread_attr_t*,
                              void* (*)(void*), void*))
      dlsym(RTLD_NEXT, "pthread_create");
  if (!g_ch) return real(out, attr, fn, arg);
  ThreadReg* r = (ThreadReg*)calloc(1, sizeof(ThreadReg));
  r->fn = fn;
  r->arg = arg;
  uint32_t out_len = 0;
  int64_t a[6] = {0, 0, 0, 0, 0, 0};
  int64_t rc = ipc_call(PSYS_THREAD_NEW, a, nullptr, 0, r->shm,
                        sizeof(r->shm) - 1, &out_len);
  if (rc < 0) {
    free(r);
    return EAGAIN;
  }
  r->shm[out_len < sizeof(r->shm) - 1 ? out_len : sizeof(r->shm) - 1] = 0;
  int ret = real(out, attr, thread_tramp, r);
  if (ret != 0) {
    free(r);  // driver-side channel leaks until process end; harmless
    return ret;
  }
  r->handle = *out;
  raw_lock(&g_threads_lock);
  r->next = g_threads;
  g_threads = r;
  raw_unlock(&g_threads_lock);
  return 0;
}

void pthread_exit(void* retval) {
  static auto real = (void (*)(void*))dlsym(RTLD_NEXT, "pthread_exit");
  thread_epilogue();  // no-op for unmanaged/main threads (t_reg unset)
  real(retval);
  raw_exit(0);  // not reached; placates noreturn
}

int pthread_join(pthread_t th, void** retval) {
  static auto real = (int (*)(pthread_t, void**))
      dlsym(RTLD_NEXT, "pthread_join");
  ThreadReg* r = g_ch ? find_thread(th) : nullptr;
  if (!r) return real(th, retval);
  // park in the driver until the trampoline flips done (the native join
  // below then returns ~immediately — the thread has left app code)
  while (r->done.load(std::memory_order_acquire) == 0)
    futex_wait_driver(&r->done, -1);
  int ret = real(th, retval);
  raw_lock(&g_threads_lock);
  ThreadReg** pp = &g_threads;
  while (*pp && *pp != r) pp = &(*pp)->next;
  if (*pp) *pp = r->next;
  raw_unlock(&g_threads_lock);
  free(r);
  return ret;
}

int pthread_mutex_lock(pthread_mutex_t* m) {
  static auto real = (int (*)(pthread_mutex_t*))
      dlsym(RTLD_NEXT, "pthread_mutex_lock");
  if (!g_ch) return real(m);
  MutexView* v = (MutexView*)m;
  int tid = my_tid();
  if ((v->kind & 3) == PTHREAD_MUTEX_RECURSIVE && v->owner == tid) {
    v->count++;
    return 0;
  }
  auto* w = (std::atomic<int>*)&v->lock;
  int expected = 0;
  if (!w->compare_exchange_strong(expected, 1)) {
    // contended: classic two-state futex mutex, waits parked in-driver
    while (w->exchange(2) != 0) futex_wait_driver(w, -1);
  }
  v->owner = tid;
  v->count = 1;
  return 0;
}

int pthread_mutex_trylock(pthread_mutex_t* m) {
  static auto real = (int (*)(pthread_mutex_t*))
      dlsym(RTLD_NEXT, "pthread_mutex_trylock");
  if (!g_ch) return real(m);
  MutexView* v = (MutexView*)m;
  int tid = my_tid();
  if ((v->kind & 3) == PTHREAD_MUTEX_RECURSIVE && v->owner == tid) {
    v->count++;
    return 0;
  }
  auto* w = (std::atomic<int>*)&v->lock;
  int expected = 0;
  if (w->compare_exchange_strong(expected, 1)) {
    v->owner = tid;
    v->count = 1;
    return 0;
  }
  return EBUSY;
}

int pthread_mutex_unlock(pthread_mutex_t* m) {
  static auto real = (int (*)(pthread_mutex_t*))
      dlsym(RTLD_NEXT, "pthread_mutex_unlock");
  if (!g_ch) return real(m);
  MutexView* v = (MutexView*)m;
  if ((v->kind & 3) == PTHREAD_MUTEX_RECURSIVE && v->count > 1) {
    v->count--;
    return 0;
  }
  v->owner = 0;
  v->count = 0;
  auto* w = (std::atomic<int>*)&v->lock;
  if (w->exchange(0) == 2) futex_wake_driver(w, 1);
  return 0;
}

// Condition variables: our representation is a bare sequence counter in
// the (zero-initialized) pthread_cond_t; wait parks in the driver until a
// signal/broadcast bumps the sequence. The driver's one-at-a-time
// scheduling means check-then-park has no lost-wakeup race: the potential
// waker cannot run between our sequence read and our park.
int pthread_cond_wait(pthread_cond_t* c, pthread_mutex_t* m) {
  static auto real = (int (*)(pthread_cond_t*, pthread_mutex_t*))
      dlsym(RTLD_NEXT, "pthread_cond_wait");
  if (!g_ch) return real(c, m);
  auto* seq = (std::atomic<unsigned>*)c;
  unsigned s = seq->load(std::memory_order_acquire);
  pthread_mutex_unlock(m);
  while (seq->load(std::memory_order_acquire) == s)
    futex_wait_driver(seq, -1);
  pthread_mutex_lock(m);
  return 0;
}

int pthread_cond_timedwait(pthread_cond_t* c, pthread_mutex_t* m,
                           const struct timespec* abstime) {
  static auto real = (int (*)(pthread_cond_t*, pthread_mutex_t*,
                              const struct timespec*))
      dlsym(RTLD_NEXT, "pthread_cond_timedwait");
  if (!g_ch) return real(c, m, abstime);
  auto* seq = (std::atomic<unsigned>*)c;
  unsigned s = seq->load(std::memory_order_acquire);
  pthread_mutex_unlock(m);
  int err = 0;
  while (seq->load(std::memory_order_acquire) == s) {
    // remaining virtual time until the absolute (sim-clock) deadline
    int64_t now = ipc_call6(SYS_clock_gettime, CLOCK_REALTIME);
    int64_t dl =
        (int64_t)abstime->tv_sec * 1000000000LL + abstime->tv_nsec;
    if (now >= dl) {
      err = ETIMEDOUT;
      break;
    }
    if (futex_wait_driver(seq, dl - now) == ETIMEDOUT &&
        seq->load(std::memory_order_acquire) == s) {
      err = ETIMEDOUT;
      break;
    }
  }
  pthread_mutex_lock(m);
  return err;
}

int pthread_cond_signal(pthread_cond_t* c) {
  static auto real = (int (*)(pthread_cond_t*))
      dlsym(RTLD_NEXT, "pthread_cond_signal");
  if (!g_ch) return real(c);
  auto* seq = (std::atomic<unsigned>*)c;
  seq->fetch_add(1, std::memory_order_acq_rel);
  futex_wake_driver(seq, 1);
  return 0;
}

int pthread_cond_broadcast(pthread_cond_t* c) {
  static auto real = (int (*)(pthread_cond_t*))
      dlsym(RTLD_NEXT, "pthread_cond_broadcast");
  if (!g_ch) return real(c);
  auto* seq = (std::atomic<unsigned>*)c;
  seq->fetch_add(1, std::memory_order_acq_rel);
  futex_wake_driver(seq, INT32_MAX);
  return 0;
}

pid_t fork(void) {
  static auto real = (pid_t (*)(void))dlsym(RTLD_NEXT, "fork");
  if (!g_ch) return real();
  char shm[160] = {0};
  uint32_t out_len = 0;
  int64_t a[6] = {0, 0, 0, 0, 0, 0};
  int64_t rc = ipc_call(PSYS_FORK, a, nullptr, 0, shm, sizeof(shm) - 1,
                        &out_len);
  if (rc < 0) {
    errno = EAGAIN;
    return -1;
  }
  shm[out_len < sizeof(shm) - 1 ? out_len : sizeof(shm) - 1] = 0;
  pid_t p = real();
  if (p < 0) {
    // native fork failed AFTER the driver registered a child: retract it
    // (a[1]=2) or the driver would wait forever for its HELLO
    int saved = errno;
    int64_t r2[6] = {0, 2, 0, 0, 0, 0};
    ipc_call(PSYS_THREAD_EXIT, r2, nullptr, 0, nullptr, 0, nullptr);
    errno = saved;
    return -1;
  }
  if (p == 0) {
    // child: single-threaded; adopt the pre-created channel (the parent's
    // mapping is inherited but belongs to the parent)
    Channel* ch = map_channel(shm);
    if (!ch) raw_exit(127);
    g_ch = ch;
    t_ch = ch;
    g_threads = nullptr;
    // a later execve must hand the CHILD's channel to the fresh image,
    // not the inherited parent path
    setenv(ENV_SHM, shm, 1);
    ch->shim_pid = getpid();
    ch->type = MSG_HELLO;
    ch->ret = getpid();
    ch->data_len = 0;
    sem_post(&ch->to_driver);
    sem_wait_spinning(&ch->to_shim, g_spin);
  }
  return p;
}

// _exit/_Exit bypass atexit/on_exit, so without interposition the driver
// would never learn the process ended (fork children have no popen handle
// to poll — they would read as wedged). Notify first, then raw-exit.
void _exit(int status) {
  if (g_ch) shim_notify_exit(status, nullptr);
  raw_exit(status);
}

void _Exit(int status) { _exit(status); }

pid_t waitpid(pid_t pid, int* wstatus, int options) {
  static auto real = (pid_t (*)(pid_t, int*, int))
      dlsym(RTLD_NEXT, "waitpid");
  if (!g_ch) return real(pid, wstatus, options);
  // Fully driver-emulated for managed fork children: the driver knows the
  // child's (deterministic, sim-time-stamped) exit and parks us until
  // then — never block natively, which would wedge the whole process.
  // WNOHANG also goes through the driver (args[1]=1): polling the NATIVE
  // child state would leak wall-clock timing into the simulation.
  int64_t a[6] = {pid, (options & WNOHANG) ? 1 : 0, 0, 0, 0, 0};
  int32_t status = 0;
  uint32_t out_len = 0;
  int64_t rc = ipc_call(PSYS_WAITPID, a, nullptr, 0, &status,
                        sizeof(status), &out_len);
  if (rc < 0) return -1;  // errno set (ECHILD)
  if (rc == 0) return 0;  // WNOHANG: no managed child done yet
  // the driver composes the full wait-status word (normal exit OR
  // signaled — see driver._wait_status); pass it through verbatim
  if (wstatus) *wstatus = status;
  real((pid_t)rc, nullptr, WNOHANG);  // opportunistic zombie reap
  return (pid_t)rc;
}

pid_t wait(int* wstatus) { return waitpid(-1, wstatus, 0); }

extern char** environ;

int execv(const char* path, char* const argv[]) {
  // glibc's execv calls execve internally (not via the PLT), so interpose
  // it explicitly and funnel into the managed execve below
  return execve(path, argv, environ);
}

int execve(const char* path, char* const argv[], char* const envp[]) {
  static auto real = (int (*)(const char*, char* const[], char* const[]))
      dlsym(RTLD_NEXT, "execve");
  if (!g_ch) return real(path, argv, envp);
  // The driver RESPAWNS the image as a fresh managed process (clean
  // seccomp state, same virtual identity) and this process exits — see
  // PSYS_EXEC in ipc.h for why native execve cannot work here. Wire
  // format: path NUL, then the FULL argv (argv[0] included — multicall
  // binaries dispatch on it) as NUL-terminated strings, then envp; argc
  // rides in args[0] so empty argv strings cannot confuse the framing.
  char buf[IPC_DATA_MAX];
  uint32_t off = 0;
  auto put = [&](const char* s) {
    size_t len = strlen(s) + 1;
    if (off + len > sizeof(buf)) return false;
    memcpy(buf + off, s, len);
    off += (uint32_t)len;
    return true;
  };
  if (!put(path)) {
    errno = E2BIG;
    return -1;
  }
  int64_t argc = 0;
  for (int j = 0; argv && argv[j]; j++, argc++)
    if (!put(argv[j])) {
      errno = E2BIG;
      return -1;
    }
  for (int j = 0; envp && envp[j]; j++)
    if (!put(envp[j])) {
      errno = E2BIG;
      return -1;
    }
  int64_t a[6] = {argc, 0, 0, 0, 0, 0};
  int64_t rc = ipc_call(PSYS_EXEC, a, buf, off, nullptr, 0, nullptr);
  if (rc < 0) return -1;  // errno set (e.g. ENOENT)
  raw_exit(0);  // replaced by the respawned image; never returns
}

}  // extern "C"

namespace {

// ---------------------------------------------------------------------------
// vDSO neutralization. The vDSO serves clock_gettime/gettimeofday/time as
// plain userspace reads of kernel-exported data — no kernel entry, so the
// seccomp backstop below never sees them, and a statically-linked binary's
// libc would read real wall-clock time, silently breaking determinism
// (ADVICE r1). Fix: locate the vDSO's exported time symbols and overwrite
// each entry point with `mov eax, <nr>; syscall; ret`. The syscall
// instruction now lives OUTSIDE the shim gate window, so the BPF traps it
// and the SIGSYS handler routes it to the emulated clock. Writes go through
// /proc/self/mem, whose FOLL_FORCE semantics bypass the vDSO VMA's write
// protection (the same trick rr uses for its vDSO monkeypatching).
// ---------------------------------------------------------------------------

struct VdsoTarget {
  const char* name;
  uint32_t nr;
};

// Upper bound on the vDSO image this parses (Linux's and gVisor's are a
// few pages).
constexpr uint64_t VDSO_MAX = 1 << 20;

void shim_patch_vdso() {
#if defined(__x86_64__)
  const char* opt = getenv(ENV_VDSO);
  if (opt && strcmp(opt, "0") == 0) return;
  uintptr_t base = (uintptr_t)getauxval(AT_SYSINFO_EHDR);
  if (!base) return;  // no vDSO mapped: nothing to neutralize
  // The ELF and program headers sit in the image's first page, which
  // AT_SYSINFO_EHDR says is mapped; everything past them is read from a
  // private copy (below), never through a pointer into the vDSO.
  const Elf64_Ehdr* eh = (const Elf64_Ehdr*)base;
  if (memcmp(eh->e_ident, ELFMAG, SELFMAG) != 0 ||
      eh->e_phoff + (uint64_t)eh->e_phnum * sizeof(Elf64_Phdr) > 4096) {
    SHIM_LOG("vdso: bad ELF or program headers; time determinism gap "
             "remains");
    return;
  }
  const Elf64_Phdr* ph = (const Elf64_Phdr*)(base + eh->e_phoff);
  uint64_t dyn_vaddr = 0, load_vaddr = UINT64_MAX, load_end = 0;
  for (int i = 0; i < eh->e_phnum; i++) {
    if (ph[i].p_type == PT_DYNAMIC) dyn_vaddr = ph[i].p_vaddr;
    if (ph[i].p_type == PT_LOAD) {
      if (ph[i].p_vaddr < load_vaddr) load_vaddr = ph[i].p_vaddr;
      if (ph[i].p_vaddr + ph[i].p_memsz > load_end)
        load_end = ph[i].p_vaddr + ph[i].p_memsz;
    }
  }
  if (!dyn_vaddr || load_vaddr == UINT64_MAX || load_end <= load_vaddr ||
      load_end - load_vaddr > VDSO_MAX || dyn_vaddr < load_vaddr) {
    SHIM_LOG("vdso: no PT_DYNAMIC/PT_LOAD; gap remains");
    return;
  }
  const uint64_t image = load_end - load_vaddr;
  int memfd = (int)sys_native(SYS_open, "/proc/self/mem", O_RDWR, 0);
  if (memfd < 0) {
    SHIM_LOG("vdso: open /proc/self/mem failed: %s; gap remains",
             strerror(errno));
    return;
  }
  // Parse a copy read through /proc/self/mem: the dynamic table, symbol
  // table and names are located by offsets that differ between kernels
  // (under gVisor, chasing them as pointers faulted inside this
  // constructor and killed every managed process), so each offset is
  // bounds-checked against the image and a bad one leaves the gap open
  // instead of faulting.
  uint8_t* img = (uint8_t*)sys_native(SYS_mmap, 0L, (long)image,
                                      PROT_READ | PROT_WRITE,
                                      MAP_PRIVATE | MAP_ANONYMOUS, -1L, 0L);
  if (img == MAP_FAILED) {
    sys_native(SYS_close, memfd);
    SHIM_LOG("vdso: no memory for the image copy; gap remains");
    return;
  }
  auto in_image = [&](uint64_t off, uint64_t len) {
    return off <= image && len <= image - off;
  };
  // a dynamic-table address: a link-time address (the usual case) or one
  // already relocated to `base`
  auto ptr_off = [&](uint64_t p, uint64_t* off) {
    if (p >= load_vaddr && p - load_vaddr < image) {
      *off = p - load_vaddr;
      return true;
    }
    if (p >= base && p - base < image) {
      *off = p - base;
      return true;
    }
    return false;
  };
  int patched = 0, failed = 0;
  uint64_t symtab = 0, strtab = 0, strsz = 0;
  bool have_sym = false, have_str = false;
  if (sys_native(SYS_pread64, memfd, img, (long)image, (long)base) !=
      (long)image) {
    SHIM_LOG("vdso: cannot read the image; gap remains");
    goto out;
  }
  for (uint64_t off = dyn_vaddr - load_vaddr;
       in_image(off, sizeof(Elf64_Dyn)); off += sizeof(Elf64_Dyn)) {
    Elf64_Dyn d;
    memcpy(&d, img + off, sizeof(d));
    if (d.d_tag == DT_NULL) break;
    if (d.d_tag == DT_SYMTAB) have_sym = ptr_off(d.d_un.d_ptr, &symtab);
    if (d.d_tag == DT_STRTAB) have_str = ptr_off(d.d_un.d_ptr, &strtab);
    if (d.d_tag == DT_STRSZ) strsz = d.d_un.d_val;
  }
  if (!have_sym || !have_str || strtab <= symtab) {
    SHIM_LOG("vdso: no dynsym/dynstr; gap remains");
    goto out;
  }
  if (!strsz || !in_image(strtab, strsz)) strsz = image - strtab;
  {
    // .dynsym is immediately followed by .dynstr in the vDSO image; the
    // gap between them bounds the symbol count (standard in-memory ELF
    // trick — there is no reliable DT_HASH on all kernels).
    const uint64_t nsyms = (strtab - symtab) / sizeof(Elf64_Sym);
    const VdsoTarget targets[] = {
        {"__vdso_clock_gettime", SYS_clock_gettime},
        {"__vdso_gettimeofday", SYS_gettimeofday},
        {"__vdso_time", SYS_time},
        {"clock_gettime", SYS_clock_gettime},
        {"gettimeofday", SYS_gettimeofday},
        {"time", SYS_time},
    };
    // Track patched addresses: aliased names (clock_gettime aliases
    // __vdso_clock_gettime) share one entry point — patch once.
    uint64_t done[sizeof(targets) / sizeof(targets[0])] = {0};
    for (uint64_t s = 0; s < nsyms; s++) {
      Elf64_Sym sym;
      memcpy(&sym, img + symtab + s * sizeof(sym), sizeof(sym));
      if (sym.st_value == 0 || sym.st_name == 0 || sym.st_name >= strsz)
        continue;
      const char* nm = (const char*)img + strtab + sym.st_name;
      const size_t nm_len = strnlen(nm, strsz - sym.st_name);
      uint64_t off;
      if (nm_len == strsz - sym.st_name || !ptr_off(sym.st_value, &off) ||
          !in_image(off, 8))
        continue;
      for (size_t t = 0; t < sizeof(targets) / sizeof(targets[0]); t++) {
        if (strcmp(nm, targets[t].name) != 0) continue;
        uintptr_t addr = base + off;
        bool seen = false;
        for (uint64_t a : done) seen |= (a == addr);
        if (seen) break;
        uint32_t nr = targets[t].nr;
        // mov eax, imm32; syscall; ret
        uint8_t stub[8] = {0xb8, (uint8_t)nr, (uint8_t)(nr >> 8),
                           (uint8_t)(nr >> 16), (uint8_t)(nr >> 24),
                           0x0f, 0x05, 0xc3};
        uint8_t back[8] = {0};
        long w = sys_native(SYS_pwrite64, memfd, stub, sizeof(stub), addr);
        long r = sys_native(SYS_pread64, memfd, back, sizeof(back), addr);
        if (w == (long)sizeof(stub) && r == (long)sizeof(back) &&
            memcmp(back, stub, sizeof(stub)) == 0) {
          for (uint64_t& a : done) {
            if (a == 0) { a = addr; break; }
          }
          patched++;
        } else {
          failed++;
          SHIM_LOG("vdso: patching %s @%#lx did not take (write %ld, "
                   "read %ld); gap remains", nm, (unsigned long)addr, w, r);
        }
        break;
      }
    }
  }
  SHIM_LOG("vdso: neutralized %d time entry points (%d failed)", patched,
           failed);
out:
  sys_native(SYS_munmap, img, (long)image);
  sys_native(SYS_close, memfd);
#endif
}

#ifndef SECCOMP_SET_MODE_FILTER
#define SECCOMP_SET_MODE_FILTER 1
#endif
#ifndef SECCOMP_FILTER_FLAG_SPEC_ALLOW
#define SECCOMP_FILTER_FLAG_SPEC_ALLOW (1UL << 2)
#endif

void shim_install_seccomp() {
#if defined(__x86_64__)
  uintptr_t gate = (uintptr_t)&shim_gate_syscall;
  uint32_t gate_lo = (uint32_t)gate;
  uint32_t gate_hi = (uint32_t)(gate >> 32);
  if (gate_lo > UINT32_MAX - GATE_WINDOW) {
    SHIM_LOG("seccomp: gate straddles a 4 GiB boundary; backstop off");
    return;
  }

  struct sigaction sa;
  memset(&sa, 0, sizeof(sa));
  sa.sa_sigaction = on_sigsys;
  // SA_NODEFER: a trapped syscall inside the handler (libc internals) must
  // re-enter it — a blocked SIGSYS under seccomp kills the process
  sa.sa_flags = SA_SIGINFO | SA_NODEFER;
  if (sigaction(SIGSYS, &sa, nullptr) != 0) {
    SHIM_LOG("seccomp: sigaction failed: %s", strerror(errno));
    return;
  }
  // An inherited mask with SIGSYS blocked would turn every trap into a
  // forced kill (reference analog: shim.c:452-458 unblocks it explicitly).
  sigset_t unblock;
  sigemptyset(&unblock);
  sigaddset(&unblock, SIGSYS);
  sigprocmask(SIG_UNBLOCK, &unblock, nullptr);

  constexpr int K = (int)(sizeof(kTrapped) / sizeof(kTrapped[0]));
  // layout: [arch check][gate IP window check][ld nr]
  //         [K dispatch jeqs → TRAP / FD0 / FD01 / STDIO] [fallthrough ALLOW]
  //         FD0: ld args[0]; >= FD_BASE ? TRAP : ALLOW
  //         FD01: ld args[0]; >= FD_BASE ? TRAP : ld args[1]; ...
  //         STDIO (write/writev when log stamping): trap the emulated fd
  //           range AND fds 1-2, so stdio writes that never cross the libc
  //           PLT (glibc stdio issues the syscall internally) still reach
  //           the stamping wrapper via SIGSYS
  //         ALLOW / TRAP / KILL returns
  const int NR = 7;
  const int DISPATCH0 = 8;
  const int FD0 = DISPATCH0 + K + 1;   // after dispatch + fallthrough ALLOW
  const int FD01 = FD0 + 2;
  const int STDIO = FD01 + 4;
  const int ALLOW = STDIO + 4;
  const int TRAP = ALLOW + 1;
  const int KILL = TRAP + 1;
  struct sock_filter prog[KILL + 1];
  const uint32_t ARG0_LO = offsetof(struct seccomp_data, args);
  const uint32_t ARG1_LO = ARG0_LO + 8;
  int i = 0;
  // non-x86-64 audit arch (e.g. int 0x80 compat syscalls) would bypass
  // virtualization with wrong syscall numbering: kill loudly instead
  prog[i++] = BPF_STMT(BPF_LD | BPF_W | BPF_ABS,
                       offsetof(struct seccomp_data, arch));
  prog[i++] = BPF_JUMP(BPF_JMP | BPF_JEQ | BPF_K, AUDIT_ARCH_X86_64, 0,
                       (uint8_t)(KILL - 2));
  prog[i++] = BPF_STMT(BPF_LD | BPF_W | BPF_ABS,
                       offsetof(struct seccomp_data, instruction_pointer) + 4);
  prog[i++] = BPF_JUMP(BPF_JMP | BPF_JEQ | BPF_K, gate_hi, 0,
                       (uint8_t)(NR - 4));
  prog[i++] = BPF_STMT(BPF_LD | BPF_W | BPF_ABS,
                       offsetof(struct seccomp_data, instruction_pointer));
  prog[i++] = BPF_JUMP(BPF_JMP | BPF_JGE | BPF_K, gate_lo, 0,
                       (uint8_t)(NR - 6));
  prog[i++] = BPF_JUMP(BPF_JMP | BPF_JGE | BPF_K, gate_lo + GATE_WINDOW,
                       (uint8_t)(NR - 7), (uint8_t)(ALLOW - 7));
  prog[i++] = BPF_STMT(BPF_LD | BPF_W | BPF_ABS,
                       offsetof(struct seccomp_data, nr));
  for (int k = 0; k < K; k++) {
    int target = kTrapped[k].act == ACT_TRAP   ? TRAP
                 : kTrapped[k].act == ACT_FD0  ? FD0
                                               : FD01;
    if (g_log_stamp &&
        (kTrapped[k].nr == SYS_write || kTrapped[k].nr == SYS_writev))
      target = STDIO;
    prog[i] = BPF_JUMP(BPF_JMP | BPF_JEQ | BPF_K,
                       (uint32_t)kTrapped[k].nr,
                       (uint8_t)(target - (i + 1)), 0);
    i++;
  }
  prog[i++] = BPF_STMT(BPF_RET | BPF_K, SECCOMP_RET_ALLOW);  // fallthrough
  // Offsets computed from explicit positions (never `i` inside a
  // `prog[i++] = ...` expression — that miscompiled to wild jumps).
  // FD0: trap iff args[0] (the fd) is in the emulated range
  prog[i++] = BPF_STMT(BPF_LD | BPF_W | BPF_ABS, ARG0_LO);
  prog[i] = BPF_JUMP(BPF_JMP | BPF_JGE | BPF_K, (uint32_t)FD_BASE,
                     (uint8_t)(TRAP - (FD0 + 2)),
                     (uint8_t)(ALLOW - (FD0 + 2)));
  i++;
  // FD01 (dup2/dup3): trap iff either fd argument is emulated
  prog[i++] = BPF_STMT(BPF_LD | BPF_W | BPF_ABS, ARG0_LO);
  prog[i] = BPF_JUMP(BPF_JMP | BPF_JGE | BPF_K, (uint32_t)FD_BASE,
                     (uint8_t)(TRAP - (FD01 + 2)), 0);
  i++;
  prog[i++] = BPF_STMT(BPF_LD | BPF_W | BPF_ABS, ARG1_LO);
  prog[i] = BPF_JUMP(BPF_JMP | BPF_JGE | BPF_K, (uint32_t)FD_BASE,
                     (uint8_t)(TRAP - (FD01 + 4)),
                     (uint8_t)(ALLOW - (FD01 + 4)));
  i++;
  // STDIO: fd >= FD_BASE → TRAP; fd >= 3 → ALLOW; fd >= 1 (1 or 2) → TRAP;
  // fd 0 → ALLOW
  prog[i++] = BPF_STMT(BPF_LD | BPF_W | BPF_ABS, ARG0_LO);
  prog[i] = BPF_JUMP(BPF_JMP | BPF_JGE | BPF_K, (uint32_t)FD_BASE,
                     (uint8_t)(TRAP - (STDIO + 2)), 0);
  i++;
  prog[i] = BPF_JUMP(BPF_JMP | BPF_JGE | BPF_K, 3,
                     (uint8_t)(ALLOW - (STDIO + 3)), 0);
  i++;
  prog[i] = BPF_JUMP(BPF_JMP | BPF_JGE | BPF_K, 1,
                     (uint8_t)(TRAP - (STDIO + 4)),
                     (uint8_t)(ALLOW - (STDIO + 4)));
  i++;
  prog[i++] = BPF_STMT(BPF_RET | BPF_K, SECCOMP_RET_ALLOW);
  prog[i++] = BPF_STMT(BPF_RET | BPF_K, SECCOMP_RET_TRAP);
#ifdef SECCOMP_RET_KILL_PROCESS
  prog[i++] = BPF_STMT(BPF_RET | BPF_K, SECCOMP_RET_KILL_PROCESS);
#else
  prog[i++] = BPF_STMT(BPF_RET | BPF_K, SECCOMP_RET_KILL);
#endif

  struct sock_fprog fprog = {(unsigned short)i, prog};
  if (prctl(PR_SET_NO_NEW_PRIVS, 1, 0, 0, 0) != 0) {
    SHIM_LOG("seccomp: no_new_privs failed: %s", strerror(errno));
    return;
  }
  // Prefer seccomp(2) with SPEC_ALLOW: plain PR_SET_SECCOMP implies
  // PR_SPEC_FORCE_DISABLE, permanently disabling speculation in every
  // managed process (reference avoids this the same way, shim.c:535-541).
  if (sys_native(SYS_seccomp, SECCOMP_SET_MODE_FILTER,
                 SECCOMP_FILTER_FLAG_SPEC_ALLOW, &fprog) != 0 &&
      prctl(PR_SET_SECCOMP, SECCOMP_MODE_FILTER, &fprog) != 0) {
    SHIM_LOG("seccomp: install failed: %s", strerror(errno));
    return;
  }
  SHIM_LOG("seccomp backstop installed (%d trapped syscalls)", K);
#endif
}

}  // namespace
